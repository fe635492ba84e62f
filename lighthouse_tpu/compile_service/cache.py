"""Persistent executable caching for the compile service.

Two cooperating layers:

* the **JAX persistent compilation cache** holds the compiled
  executables themselves — a restarted node's AOT warmup walk finds
  every staged program on disk and "compiles" in milliseconds instead
  of minutes. Its directory is placed from outside:
  ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself, and no
  code here overrides it), otherwise, on a TPU, the fixed
  ``<repo>/.jax_cache``. A node on any other platform keeps no cache
  unless that variable asks for one: XLA:CPU cache entries can
  round-trip with mismatched machine features and crash on load.
* a **manifest** (``manifest.json`` next to the cache entries) records
  WHICH rungs were baked under WHICH environment, keyed on
  backend platform | jax version | device-code hash | fp_impl |
  (B, K, M) | stage. The executables alone cannot answer "is this cache
  warm for ME?" — the manifest can, and a key mismatch (engine switch,
  device-code edit, jax upgrade) is a MISS by construction, so a stale
  bake can never masquerade as a warm start
  (``tests/test_compile_service.py`` pins the invalidation).

The test suite turns the cache off (``jax_enable_compilation_cache``)
for the same reason (``tests/conftest.py``); with the cache off
:func:`enable_persistent_cache` reports it disabled and no manifest is
kept.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
MANIFEST_NAME = "manifest.json"
MANIFEST_SCHEMA = "lighthouse_tpu.compile_manifest/1"

# The device modules whose source defines the staged programs: an edit
# to any of them changes the emitted HLO, so it must change the cache
# key. Order is part of the hash input (kept sorted).
_CODE_MODULES = (
    "bls", "curve", "fp", "fp2", "htc", "pairing", "pallas_fp",
    "pallas_fp2", "tower",
)


def cache_dir() -> str:
    """The cache directory: ``JAX_COMPILATION_CACHE_DIR`` when set,
    otherwise ``<repo>/.jax_cache`` (used on a TPU only)."""
    return os.environ.get(ENV_CACHE_DIR) or DEFAULT_CACHE_DIR


def enable_persistent_cache() -> dict:
    """Turn on JAX's persistent compilation cache in :func:`cache_dir`
    and persist every compile (``min_compile_time`` 0: JAX's default 1 s
    floor would skip small rungs whose manifest entries still claimed a
    warm start). Returns ``{enabled, dir, reason}``; ``enabled`` is False
    where ``jax_enable_compilation_cache`` was turned off, and on a
    non-TPU platform unless ``JAX_COMPILATION_CACHE_DIR`` is set."""
    import jax

    path = cache_dir()
    if not jax.config.jax_enable_compilation_cache:
        return {
            "enabled": False, "dir": path,
            "reason": "jax_enable_compilation_cache is off",
        }
    if not os.environ.get(ENV_CACHE_DIR):
        platform = jax.default_backend()
        if platform != "tpu":
            return {
                "enabled": False, "dir": path,
                "reason": f"{platform} node without {ENV_CACHE_DIR}",
            }
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return {"enabled": True, "dir": path, "reason": None}


_code_hash: str | None = None


def code_version_hash() -> str:
    """Hash of the device crypto sources that define the staged
    programs (12 hex chars). Any edit to them invalidates every
    manifest key — the executables in the jax cache key on the real HLO
    fingerprint; the manifest must be at least as conservative. The
    sources cannot change under a running process, so the hash is
    computed once and memoized — ``environment_key`` sits on the
    /lighthouse/health scrape path."""
    global _code_hash
    if _code_hash is None:
        import lighthouse_tpu.crypto.device as _device

        h = hashlib.sha256()
        base = os.path.dirname(os.path.abspath(_device.__file__))
        for mod in _CODE_MODULES:
            path = os.path.join(base, mod + ".py")
            try:
                with open(path, "rb") as f:
                    h.update(f.read())
            except OSError:
                h.update(b"missing:" + mod.encode())
        _code_hash = h.hexdigest()[:12]
    return _code_hash


def environment_key(
    fp_impl: str,
    platform: str | None = None,
    jax_version: str | None = None,
    code_hash: str | None = None,
) -> str:
    """The environment half of a manifest key. The defaults describe
    THIS process (lazily querying jax); tests inject explicit parts to
    pin the invalidation semantics without a backend."""
    if platform is None or jax_version is None:
        import jax

        platform = platform or jax.default_backend()
        jax_version = jax_version or jax.__version__
    code_hash = code_hash or code_version_hash()
    return f"{platform}|jax-{jax_version}|code-{code_hash}|{fp_impl}"


def manifest_key(
    env_key: str, stage: str, b: int, k: int, m: int, device: int = 0
) -> str:
    """Device 0 keeps the pre-mesh key (existing manifests stay valid);
    a mesh walk's other chips key with a ``dev{n}`` component — their
    executables are distinct cache entries (a compile is per device
    assignment), so their warm-start claims must be too."""
    base = f"{env_key}|B{b}K{k}M{m}"
    if device:
        base += f"|dev{int(device)}"
    return f"{base}|{stage}"


def executable_entries(cache_dir: str) -> set | None:
    """``(name, mtime_ns)`` of the executable entries currently in
    ``cache_dir`` (the manifest and atomic-write temp files excluded);
    None when the dir is unreadable. The before/after probe both the
    service's AOT walk and the warmup CLI use to keep the manifest at
    least as conservative as the cache. Snapshotting mtimes (not just
    names) lets a re-warm over an already-baked cache count as
    persisted when the load path touches its entries — a manifest lost
    after a successful bake can then be rebuilt without wiping the
    cache."""
    try:
        with os.scandir(cache_dir) as it:
            return {
                (e.name, e.stat().st_mtime_ns)
                for e in it
                if e.name != MANIFEST_NAME and not e.name.endswith(".tmp")
            }
    except OSError:
        return None


def persisted_after(cache_dir: str, before: set | None, any_fresh: bool) -> bool:
    """Did a compile walk actually involve the executable cache? True
    unless a FRESH compile left the cache dir byte-for-byte untouched —
    no new entries AND no existing entry touched — which is what a
    silent write failure looks like. Conservative residual: a cache-
    served re-warm whose load path touches nothing reads as
    not-persisted, so a lost manifest may stay unreported until a fresh
    bake (warm-start claims err cold, never warm)."""
    if before is None or not any_fresh:
        return True
    after = executable_entries(cache_dir)
    return after is None or bool(after - before)


class Manifest:
    """Thread-safe record of baked rungs, persisted as one JSON file in
    the cache directory. ``has(key)`` answers warm-start questions;
    ``add(key)`` is called by the compile worker after each successful
    stage compile. A missing/corrupt file reads as empty (a lost
    manifest only costs re-warming, never correctness)."""

    def __init__(self, cache_dir: str):
        self.path = os.path.join(cache_dir, MANIFEST_NAME)
        self._lock = threading.Lock()
        self._entries: dict = {}
        self._load()

    def _load(self) -> None:
        try:
            with open(self.path) as f:
                doc = json.load(f)
            if doc.get("schema") == MANIFEST_SCHEMA:
                self._entries = dict(doc.get("entries", {}))
        except (OSError, ValueError):
            self._entries = {}

    def _save_locked(self) -> None:
        doc = {"schema": MANIFEST_SCHEMA, "entries": self._entries}
        tmp = self.path + ".tmp"
        try:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)  # atomic: readers never see a torn file
        except OSError:
            pass  # best-effort: the jax cache still holds the executables

    def has(self, key: str) -> bool:
        with self._lock:
            return key in self._entries

    def add(self, key: str, **meta) -> None:
        self.add_many((key,), **meta)

    def add_many(self, keys, **meta) -> None:
        """Record several keys in ONE file rewrite — a rung's readiness
        adds its three stage keys together, so per-key ``add`` would
        fsync-replace the whole manifest three times back to back."""
        with self._lock:
            for key in keys:
                self._entries[key] = dict(meta)
            self._save_locked()

    def entries(self) -> dict:
        with self._lock:
            return dict(self._entries)

    def prebaked_rungs(self, env_key: str, stages=("stage1", "stage2", "stage3")) -> list:
        """Rungs (B, K, M) whose EVERY stage is recorded under
        ``env_key`` — the rungs a restarted node re-warms from disk with
        zero fresh XLA work."""
        prefix = env_key + "|"
        with self._lock:
            shapes: dict = {}
            for key in self._entries:
                if not key.startswith(prefix):
                    continue
                try:
                    shape_part, stage = key[len(prefix):].split("|")
                    b, rest = shape_part[1:].split("K")
                    k, m = rest.split("M")
                    rung = (int(b), int(k), int(m))
                except ValueError:
                    continue
                shapes.setdefault(rung, set()).add(stage)
        return sorted(r for r, st in shapes.items() if st >= set(stages))
