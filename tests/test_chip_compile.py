"""Compiles for a described TPU v5e, with no chip attached.

The TPU compiler installed with JAX compiles for a topology that is
described rather than attached, so what the chip's compiler (and Mosaic,
for the Pallas kernels) would refuse fails here at zero chip time:
every Pallas kernel in ``crypto/device/``, ``fp.mul`` under the default
engine, the key-table gather program and stage 2 of the staged verify
program at the 64x128x16 rung. Stages 1 and 3 take 30-45 s each to
compile and are compiled by hand (see ``chip_smoke.py``), not here.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the suite runs under
several workers (each imports every test file). All compiles stay in
this one file so a single worker holds the library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from lighthouse_tpu.crypto.device import fp

LANES = 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernels as the chip runs them: Mosaic-compiled, not
    interpreted (the CPU platform would pick interpret mode)."""
    from lighthouse_tpu.crypto.device import pallas_fp, pallas_fp2

    monkeypatch.setattr(pallas_fp, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_fp2, "_interpret", lambda: False)


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem is not None
    # one v5e chip holds 16 GB of HBM
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    return compiled


@pytest.mark.parametrize("kernel", ["mul_cols_int8", "mul2", "sq2"])
def test_pallas_kernel_compiles_for_v5e(one_chip, compiled_kernels, kernel):
    from lighthouse_tpu.crypto.device import pallas_fp, pallas_fp2

    limbs = _shape((LANES, fp.NL), jnp.int32, one_chip)
    fp2_elems = _shape((LANES, 2, fp.NL), jnp.int32, one_chip)
    fn, args = {
        "mul_cols_int8": (pallas_fp.mul_cols_int8, (limbs, limbs)),
        "mul2": (pallas_fp2.mul2, (fp2_elems, fp2_elems)),
        "sq2": (pallas_fp2.sq2, (fp2_elems,)),
    }[kernel]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_fp_mul_default_engine_compiles_for_v5e(one_chip):
    assert fp.get_impl() == fp.IMPL_TOEPLITZ_INT32
    x = _shape((4096, fp.NL), jnp.int32, one_chip)
    _compile(fp.mul, x, x)


def test_key_table_gather_compiles_for_v5e(one_chip):
    from lighthouse_tpu.crypto.device import bls as dbls

    table = _shape((65536, 2, fp.NL), jnp.int32, one_chip)
    agg = _shape((4096, 2, fp.NL), jnp.int32, one_chip)
    idx = _shape((64, 128), jnp.int32, one_chip)
    _compile(dbls._gather_fn, table, agg, idx)


def test_stage2_compiles_for_v5e(one_chip):
    from lighthouse_tpu.compile_service import lowering

    fn, dummy = lowering.staged_programs(64, 128, 16)["stage2"]
    args = [_shape(a.shape, a.dtype, one_chip) for a in dummy]
    _compile(fn, *args)
