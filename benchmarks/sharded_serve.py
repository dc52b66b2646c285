"""Sharded serving sweep: layout x device count.

The paper's scale-out regime (§7, Table 9) on the jax plane: the quantized
backing store is sharded across a ``('shard',)`` mesh in the *row* layout
(misses resolved locally, pooled partials psum-combined) and the *table*
layout (whole tables per shard, outputs all-gathered), and a trace is
served through ``ShardedServingEngine.serve_columnar`` at 1/2/4/8 shards.

Reported per cell: warm-path us/query, max pooled error vs the
single-device engine (f32 summation-order noise only), and whether the
summed ``sm_ios`` match the single-device accounting exactly (they must —
ownership partitions the per-shard miss dedupes).

Where the process has accelerator devices, the sweep runs in this process
over them (device counts above what exists are left out): one process per
chip. On the CPU it runs in a child process with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, since the forced
device count must be set before jax initializes and the benchmark harness
has usually initialized jax (1 CPU device) long before this suite runs.
CPU timings are simulator run time — shard_map over forced host devices
measures orchestration, not ICI collectives.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time
from typing import Sequence

from benchmarks.common import emit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sweep(num_queries: int, chunk: int, device_counts: Sequence[int],
          layouts: Sequence[str]) -> dict:
    """Serve one trace through the single-device engine and every
    (layout, device count) sharded engine; returns the grid."""
    import numpy as np

    from repro.core.io_sim import DEVICES
    from repro.launch.mesh import make_embed_mesh
    from repro.runtime.engine import DeviceServingEngine, EngineConfig
    from repro.runtime.sharded_engine import ShardedServingEngine
    from repro.workloads.archetypes import ARCHETYPES, build_trace

    spec = ARCHETYPES["zipf_steady"]
    spec = dataclasses.replace(
        spec, num_queries=num_queries,
        tenants=tuple(dataclasses.replace(
            t, table_bytes=1e6, num_user_tables=4, num_item_tables=2)
            for t in spec.tenants))
    trace = build_trace(spec)
    rng = np.random.default_rng(0)
    tables = {m.table_id: rng.standard_normal(
        (m.num_rows, 32)).astype(np.float32) for m in trace.all_metas()}
    cfg = EngineConfig(hbm_cache_bytes=4 << 20, use_kernels=False)
    chunks = [ch.columnar for ch in trace.chunks(chunk)]

    def serve(eng):
        pooled = [eng.serve_columnar(ch)[0] for ch in chunks]  # compile+cold
        t0 = time.perf_counter()
        for ch in chunks:                                       # warm timing
            eng.serve_columnar(ch)
        return time.perf_counter() - t0, pooled

    base = DeviceServingEngine(tables, DEVICES["optane_ssd"], cfg)
    dt, p_base = serve(base)
    nq = len(trace)
    out = {"num_queries": nq, "layouts": list(layouts),
           "device_counts": list(device_counts),
           "single_us_per_query": round(dt * 1e6 / nq, 2), "grid": {}}
    for layout in layouts:
        for n in device_counts:
            eng = ShardedServingEngine(
                tables, DEVICES["optane_ssd"], cfg,
                mesh=make_embed_mesh(n), layout=layout)
            dt, pooled = serve(eng)
            err = max(float(np.max(np.abs(a - b))) if a.size else 0.0
                      for a, b in zip(pooled, p_base))
            out["grid"][f"{layout}/n{n}"] = {
                "us_per_query": round(dt * 1e6 / nq, 2),
                "max_err_vs_single": err,
                "sm_ios": eng.stats.sm_ios,
                "ios_match": bool(eng.stats.sm_ios == base.stats.sm_ios),
                "hit_rate": round(eng.hit_rate, 4),
            }
    return out


def run(num_queries: int = 256, chunk: int = 32,
        device_counts: Sequence[int] = (1, 2, 4, 8),
        layouts: Sequence[str] = ("row", "table")) -> dict:
    import jax
    params = {"num_queries": num_queries, "chunk": chunk,
              "device_counts": list(device_counts),
              "layouts": list(layouts)}
    if jax.default_backend() != "cpu":
        params["device_counts"] = [n for n in device_counts
                                   if n <= len(jax.devices())]
        out = sweep(**params)
    else:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"),
                                               ROOT]))
        r = subprocess.run(
            [sys.executable, "-c",
             "import json, sys; from benchmarks.sharded_serve import sweep; "
             "print(json.dumps(sweep(**json.loads(sys.argv[1]))))",
             json.dumps(params)],
            env=env, capture_output=True, text=True, timeout=900)
        if r.returncode != 0:
            raise RuntimeError(
                f"sharded_serve subprocess failed:\n{r.stderr[-2000:]}")
        out = json.loads(r.stdout.strip().splitlines()[-1])
    for key, cell in out["grid"].items():
        emit(f"sharded_serve_{key.replace('/', '_')}",
             cell["us_per_query"],
             f"err={cell['max_err_vs_single']:.1e};"
             f"ios_match={cell['ios_match']};hit={cell['hit_rate']}")
    return out


if __name__ == "__main__":
    print(json.dumps(run(), indent=2))
