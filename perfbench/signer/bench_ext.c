/* The benchmark's traffic signer, over the BLS12-381 code in bls12381.c.
 *
 * bls12381.c and bls12381_tables.h are a frozen copy of the program's C
 * engine (lighthouse_tpu/_native/), kept with the benchmark so that the
 * signer never changes with the program. It decides no verdict: the
 * pure-Python reference in ../ref/bls.py judges every one. This file
 * adds what traffic generation needs at registry scale:
 *
 *   bench_keygen      [sk0 + i] G1 for i < n, as raw affine x||y; one
 *                     point addition per key and one field inversion
 *                     per chunk of 1024 keys (Montgomery's trick);
 *   bench_sign_batch  [sk_i] H(msg) for many secret keys over ONE
 *                     message, hashing the message once;
 *   bench_g2_shift    compressed signature + t * H(dmsg), t = +1 or -1:
 *                     the two halves of a pair of signatures whose
 *                     errors cancel in an unweighted sum.
 *
 * Build: cc -O3 -fPIC -shared bench_ext.c (includes bls12381.c).
 * Call bench_init() once before calling anything from several threads.
 */

#include "bls12381.c"

static void sk_to_limbs(uint64_t e[4], const uint8_t sk_be[32]) {
    for (int i = 0; i < 4; i++) {
        e[i] = 0;
        for (int j = 0; j < 8; j++)
            e[i] |= (uint64_t)sk_be[32 - 8 * (i + 1) + (7 - j)] << (8 * j);
    }
}

static void g2_mul_256(g2p *o, const g2p *p, const uint64_t e[4]) {
    g2p s;
    g2_set_inf(&s);
    int started = 0;
    for (int i = 3; i >= 0; i--)
        for (int b = 63; b >= 0; b--) {
            if (started) g2_dbl(&s, &s);
            if ((e[i] >> b) & 1) {
                if (!started) { s = *p; started = 1; }
                else g2_add(&s, &s, p);
            }
        }
    *o = s;
}

int bench_init(void) {
    ensure_init();
    return 1;
}

int bench_keygen(const uint8_t sk0_be[32], uint32_t n, uint8_t *out_xy) {
    enum { CH = 1024 };
    static __thread g1p pts[CH];
    static __thread fp pre[CH];
    ensure_init();
    uint64_t e[4];
    sk_to_limbs(e, sk0_be);
    g1p g, p;
    g1_from_affine(&g, &G1_GEN);
    g1_mul(&p, &g, e, 4);
    for (uint32_t base = 0; base < n; base += CH) {
        uint32_t m = (n - base) < CH ? (n - base) : CH;
        fp acc = FP_ONE;
        for (uint32_t j = 0; j < m; j++) {
            if (p.inf || fp_is_zero(&p.z)) return 0;
            pts[j] = p;
            pre[j] = acc;
            fp_mul(&acc, &acc, &p.z);
            g1_add(&p, &p, &g);
        }
        fp inv;
        fp_inv(&inv, &acc);
        for (uint32_t jj = m; jj-- > 0;) {
            fp zi, zi2, zi3, x, y;
            fp_mul(&zi, &inv, &pre[jj]);
            fp_mul(&inv, &inv, &pts[jj].z);
            fp_sqr(&zi2, &zi);
            fp_mul(&zi3, &zi2, &zi);
            fp_mul(&x, &pts[jj].x, &zi2);
            fp_mul(&y, &pts[jj].y, &zi3);
            uint8_t *o = out_xy + (uint64_t)(base + jj) * 96;
            fp_to_bytes(o, &x);
            fp_to_bytes(o + 48, &y);
        }
    }
    return 1;
}

int bench_sign_batch(const uint8_t *msg, uint32_t msg_len,
                     const uint8_t *dst, uint32_t dst_len,
                     uint32_t n, const uint8_t *sks_be, uint8_t *out_sigs) {
    ensure_init();
    g2a h;
    hash_to_g2(&h, msg, msg_len, dst, dst_len);
    g2p hp;
    g2_from_affine(&hp, &h);
    for (uint32_t i = 0; i < n; i++) {
        uint64_t e[4];
        sk_to_limbs(e, sks_be + (uint64_t)i * 32);
        g2p s;
        g2_mul_256(&s, &hp, e);
        g2a sa;
        if (s.inf) { sa.inf = 1; sa.x = FP2_ZERO; sa.y = FP2_ZERO; }
        else g2_to_affine(&sa, &s);
        g2_compress(out_sigs + (uint64_t)i * 96, &sa);
    }
    return 1;
}

int bench_g2_shift(const uint8_t sig[96], const uint8_t *dmsg, uint32_t dmsg_len,
                   const uint8_t *dst, uint32_t dst_len, int sign,
                   uint8_t out_sig[96]) {
    ensure_init();
    g2a a, d;
    if (!g2_decompress(&a, sig)) return 0;
    hash_to_g2(&d, dmsg, dmsg_len, dst, dst_len);
    g2p pa, pd, s;
    g2_from_affine(&pa, &a);
    g2_from_affine(&pd, &d);
    if (sign < 0) g2_neg(&pd, &pd);
    g2_add(&s, &pa, &pd);
    g2a sa;
    if (s.inf) { sa.inf = 1; sa.x = FP2_ZERO; sa.y = FP2_ZERO; }
    else g2_to_affine(&sa, &s);
    g2_compress(out_sig, &sa);
    return 1;
}
