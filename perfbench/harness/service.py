"""The node's BLS verification service, assembled as ``ClientBuilder``
assembles it for ``bls_backend="tpu"`` (``lighthouse_tpu/client.py``):
a one-chip device mesh, the device key table synced from a pubkey cache
holding the whole registry, a compile service pinned to the cell's
rungs, and the verification scheduler with its planner on. The chain,
HTTP and duty-lookahead layers are left out: the window drives the
scheduler directly.
"""

from __future__ import annotations

import time
import types


class Service:
    def __init__(self, cfg: dict, reg, rungs, verify_fn=None,
                 backend: str = "tpu", compile_service: bool = True):
        from lighthouse_tpu.compile_service import CompileService, set_service
        from lighthouse_tpu.crypto import backend as backends
        from lighthouse_tpu.crypto.device import key_table, mesh as mesh_mod
        from lighthouse_tpu.verification_service import VerificationScheduler

        self.phases = {}
        backends.set_backend(backend)
        self.mesh = mesh_mod.DeviceMesh(n_devices=1)
        mesh_mod.set_mesh(self.mesh)
        if mesh_mod.recovery_env_enabled():
            self.mesh.start_recovery()
        t = time.perf_counter()
        # the registry stands in for the chain's ValidatorPubkeyCache:
        # an append-only list of the admitted PublicKey objects
        self.cache = types.SimpleNamespace(pubkeys=reg.pubkeys)
        self.table = key_table.DeviceKeyTable(self.cache)
        self.table.sync(reason="startup")
        key_table.set_table(self.table)
        self.phases["key_table_sync_s"] = time.perf_counter() - t
        self.rungs = [tuple(r) for r in rungs]
        self.compile = None
        if compile_service:
            self.compile = CompileService(rungs=self.rungs)
            self.compile.start()
            set_service(self.compile)
        sched = cfg["scheduler"]
        self.scheduler = VerificationScheduler(
            verify_fn=verify_fn,
            deadline_ms=sched["deadline_ms"],
            max_batch_sets=sched["max_batch_sets"],
            max_queue_sets=sched["max_queue_sets"],
            compile_service=self.compile,
        ).start()

    def wait_warm(self, timeout_s: float) -> list:
        """Block until every pinned rung is warm; returns one record per
        rung (seconds, per stage, and whether the persistent cache served
        it)."""
        from lighthouse_tpu.crypto.device import fp
        from lighthouse_tpu.utils import flight_recorder

        if self.compile is None:
            return []
        deadline = time.monotonic() + timeout_s
        impl = fp.get_impl()
        while not all(self.compile.registry.is_warm(r, impl) for r in self.rungs):
            failed = flight_recorder.events(kinds=("compile_failed",))
            if failed:
                raise RuntimeError(f"compile failed: {failed[-1]['fields']}")
            if time.monotonic() > deadline:
                raise TimeoutError("rungs still cold after set-up limit")
            time.sleep(0.05)
        out = []
        for e in flight_recorder.events(kinds=("compile_ready",)):
            f = e["fields"]
            out.append({
                "rung": [f["b"], f["k"], f["m"]],
                "seconds": f["seconds"],
                "stage_s": [f.get("stage1_s"), f.get("stage2_s"), f.get("stage3_s")],
            })
        return out

    def stop(self) -> None:
        from lighthouse_tpu.compile_service import clear_service
        from lighthouse_tpu.crypto.device import key_table, mesh as mesh_mod

        self.scheduler.stop()
        if self.compile is not None:
            self.compile.stop()
            clear_service(self.compile)
        key_table.clear_table(self.table)
        self.table.close()
        self.mesh.stop_recovery()
        mesh_mod.clear_mesh(self.mesh)
