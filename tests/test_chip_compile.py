"""Compiles for a described TPU v5e, with no chip attached.

The TPU compiler installed with JAX compiles for a topology that is
described rather than attached, so what the chip's compiler (and Mosaic,
for the Pallas kernels) would refuse fails here at zero chip time:
every Pallas kernel in ``crypto/device/``, ``fp.mul`` under the default
engine, the key-table gather program and stage 2 of the staged verify
program at the 64x128x16 rung. Stages 1 and 3 take 30-45 s each to
compile and are compiled by hand (see ``chip_smoke.py``), not here.

The gather and stage-2 compiles also pin the programs' named scopes
(``crypto/device/scopes.py``): every top-level operation of the
optimised program lies under its program's top scope, the inner scopes
are the catalogued ones, and the optimised instruction counts are
pinned (scopes are metadata only and leave them as they were).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the suite runs under
several workers (each imports every test file). All compiles stay in
this one file so a single worker holds the library.
"""

import os
import re

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


@pytest.fixture(scope="module")
def gather_compiled(one_chip):
    from lighthouse_tpu.crypto.device import bls as dbls

    table = _shape((65536, 2, fp.NL), jnp.int32, one_chip)
    agg = _shape((4096, 2, fp.NL), jnp.int32, one_chip)
    idx = _shape((64, 128), jnp.int32, one_chip)
    return _compile(dbls._gather_fn, table, agg, idx)


@pytest.fixture(scope="module")
def stage2_compiled(one_chip):
    from lighthouse_tpu.compile_service import lowering

    fn, dummy = lowering.staged_programs(64, 128, 16)["stage2"]
    args = [_shape(a.shape, a.dtype, one_chip) for a in dummy]
    return _compile(fn, *args)


def test_key_table_gather_compiles_for_v5e(gather_compiled):
    assert "gather" in gather_compiled.as_text()


def test_stage2_compiles_for_v5e(stage2_compiled):
    assert "while" in stage2_compiled.as_text()


# program -> (top scope, its inner scopes, optimised HLO instructions at
# the compiled shapes; scopes are metadata and do not change the counts)
SCOPES = {
    "gather": ("bls.gather", set(), 133),
    "stage2": (
        "bls.stage2", {"key_sum", "g2_ladder", "psi_check", "g1_ladder"},
        26339,
    ),
}


def _entry_ops(text):
    """(name, opcode, op_name) of the entry computation's instructions."""
    entry = re.search(r"^ENTRY .*?^}", text, re.S | re.M).group(0)
    out = []
    for ln in entry.splitlines()[1:-1]:
        name = ln.split(" = ")[0].replace("ROOT", "").strip().lstrip("%")
        opcode = re.search(r" ([a-z][a-z0-9-]*)\(", ln.split(" = ", 1)[1])
        op_name = re.search(r'op_name="([^"]*)"', ln)
        out.append((name, opcode.group(1) if opcode else "",
                    op_name.group(1) if op_name else ""))
    return out


@pytest.mark.parametrize("program", sorted(SCOPES))
def test_stage_program_ops_map_to_named_scopes(
    program, gather_compiled, stage2_compiled
):
    from lighthouse_tpu.compile_service import lowering

    compiled = {"gather": gather_compiled, "stage2": stage2_compiled}[program]
    top, inner, instructions = SCOPES[program]
    text = compiled.as_text()
    assert lowering.hlo_instruction_count(text) == instructions
    ops = _entry_ops(text)
    scoped = [op for op in ops if f"/{top}/" in op[2]]
    loops = [op for op in ops if op[1] == "while"]
    # the program's own operations carry its top scope and no other
    for name, opcode, op_name in ops:
        if op_name.startswith("jit("):
            assert f"/{top}/" in op_name, (name, opcode, op_name)
    assert scoped and all(f"/{top}/" in op[2] for op in loops)
    found = {op[2].split(f"/{top}/")[1].split("/")[0] for op in scoped}
    named = found & inner
    assert named == inner, f"inner scopes missing: {inner - named}"
