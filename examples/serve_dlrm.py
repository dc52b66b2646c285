"""Serve a trained DLRM with SDM tiering, batched end to end: user embeddings
on SM (Nand model) behind the FM row cache + pooled cache, item embeddings +
MLPs on FM, batched item ranking per query (Eq. 2: B_U=1, B_I large),
inter-op-parallel IO with the event-driven admission ledger, and a power/QPS
report per the paper's Table 8 methodology.

Queries flow through two data planes and both are exercised here:

* host plane   — ``ServeScheduler.serve_batch`` over ``SDMEmbeddingStore``:
                 vectorized probe/IO accounting for the big virtual tables.
* device plane — ``DeviceServingEngine``: the model's real user tables,
                 int8-quantized in the simulated SM tier, served through the
                 ``cache_probe`` + ``gather_pool`` Pallas kernels with an HBM
                 row cache (numerics checked against the numpy oracle).

The host-plane traffic comes from the workload engine: pick any archetype
from ``repro.workloads.ARCHETYPES`` (steady Zipf, popularity drift, diurnal,
MMPP-bursty, multi-tenant) and its trace — M1-statistics tables, timed
arrivals, stored columnar (CSR) — drives ``serve_columnar`` chunk by chunk
through the vectorized data plane and admission ledger.

The SM latency plane is selectable: ``--latency-mode analytic`` (default)
prices IO with the closed-form loaded-latency means; ``--latency-mode
sampled`` routes it through the event-driven device simulator
(``src/repro/devices/``) — seeded queues, sampled service, and optionally a
background model-update write stream (``--updating``) with the §4.1 tuning
knobs (``--tuned``: outstanding-IO throttle + read-priority scheduling).

Run: PYTHONPATH=src python examples/serve_dlrm.py \
         [--queries 128 --batch 32 --archetype zipf_steady]
         [--latency-mode sampled --updating --tuned]
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DEVICES, SDMConfig, SDMEmbeddingStore
from repro.core.power import HW_L, HW_SS, Workload, run_scenario
from repro.devices import DeviceTuning, UpdateSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.models import dlrm
from repro.runtime.engine import DeviceServingEngine, EngineConfig
from repro.runtime.serve_sched import ServeConfig, ServeScheduler
from repro.workloads import ARCHETYPES, build_trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=128)
    ap.add_argument("--batch", type=int, default=32, help="serving batch size")
    ap.add_argument("--item-batch", type=int, default=50)
    ap.add_argument("--archetype", default="zipf_steady",
                    choices=sorted(ARCHETYPES))
    ap.add_argument("--latency-mode", default="analytic",
                    choices=("analytic", "sampled"),
                    help="SM latency plane: closed-form means or the "
                         "event-driven device simulator")
    ap.add_argument("--updating", action="store_true",
                    help="sampled mode: stream endurance-bounded model-update"
                         " writes into the device plane")
    ap.add_argument("--tuned", action="store_true",
                    help="sampled mode: apply the §4.1 tuning knobs "
                         "(outstanding-IO throttle + read-priority)")
    args = ap.parse_args()
    enable_compile_cache()

    # model (small, materialized) + SDM inventory (M1-statistics, virtual)
    arch = dlrm.DLRMArch(user_tables=(50_000,) * 6, item_tables=(50_000,) * 3,
                         embed_dim=32, pooling=8,
                         bottom_mlp=(128, 64, 32), top_mlp=(128, 1))
    params = dlrm.init_params(arch, jax.random.PRNGKey(1))
    rng = np.random.default_rng(2)

    # host-plane traffic: the chosen archetype at the example's M1 scale
    # (61 user + 30 item tables, 4 GB inventory — Table 6 statistics)
    spec = ARCHETYPES[args.archetype]
    spec = dataclasses.replace(
        spec, num_queries=args.queries,
        tenants=tuple(dataclasses.replace(
            t, model="dlrm-m1", num_user_tables=61, num_item_tables=30,
            table_bytes=4e9) for t in spec.tenants))
    if args.latency_mode == "sampled":
        # the event-driven queues are honest about device capacity: the full
        # 61-table M1 inventory saturates a 2-device Nand plane past a few
        # hundred QPS (the paper serves M1 at 240 QPS/host, Table 8), so the
        # sampled demo offers the paper's per-host rate
        spec = dataclasses.replace(spec, arrival=dataclasses.replace(
            spec.arrival, rate_qps=240.0))
    trace = build_trace(spec)
    store = SDMEmbeddingStore(
        trace.all_metas(), DEVICES["nand_flash"],
        SDMConfig(fm_cache_bytes=128 << 20, pooled_cache_bytes=16 << 20,
                  latency_mode=args.latency_mode,
                  update=(UpdateSpec(model_size_gb=1000.0)
                          if args.updating else None),
                  tuning=(DeviceTuning(max_outstanding=12, read_priority=True)
                          if args.tuned else None)),
        seed=3)
    sched = ServeScheduler(store, ServeConfig(inter_op_parallel=True,
                                              item_compute_us=200.0))

    # device plane: the DLRM's user tables behind the HBM row cache
    n_user = len(arch.user_tables)
    engine = DeviceServingEngine(
        {i: np.asarray(params["tables"][i]) for i in range(n_user)},
        DEVICES["nand_flash"], EngineConfig(hbm_cache_bytes=4 << 20))

    serve = jax.jit(lambda p, u, it, d: dlrm.serve_query(p, u, it, d, arch))
    Bi = args.item_batch
    scores_sum = 0.0
    max_dev_err = 0.0
    done = 0
    for ch in trace.chunks(args.batch):
        nb = len(ch.arrival_us)
        # SDM host plane: the chunk's columnar (CSR) view goes straight
        # through the vectorized data plane — per-table segment slices from
        # the trace-level grouping, admission ledger retired vectorized at
        # the trace's arrival times
        sched.serve_columnar(ch.columnar, bg_iops=10_000,
                             arrivals_us=ch.arrival_us, collect=False)
        # device plane: pooled user embeddings for the same nb queries
        u_idx = rng.integers(0, 50_000, (nb, n_user, arch.pooling))
        pooled, _ = engine.serve_batch(u_idx, bg_iops=10_000)
        max_dev_err = max(max_dev_err,
                          float(np.abs(pooled - engine.reference_pool(u_idx)).max()))
        # compute side: actual CTR scores for the item batch of one query
        it_idx = jnp.asarray(rng.integers(0, 50_000, (3, Bi, arch.pooling)), jnp.int32)
        dense = jnp.asarray(rng.standard_normal((Bi, arch.num_dense)), jnp.float32)
        scores = serve(params, jnp.asarray(u_idx[0], jnp.int32), it_idx, dense)
        scores_sum += float(scores.mean())
        done += nb

    print(f"served {done} queries of trace '{trace.name}' "
          f"(batch={args.batch}, offered {trace.offered_qps:.0f} QPS) "
          f"x {Bi} items")
    print(f"  SM latency plane:    {args.latency_mode}"
          + (f" (updating={args.updating}, tuned={args.tuned})"
             if args.latency_mode == "sampled" else ""))
    if store.io.sim is not None and store.io.sim.update is not None:
        u = store.io.sim.update
        print(f"  update write plane:  {u.waves} waves, {u.gc_events} GC "
              f"pauses")
    print(f"  p50/p95/p99 latency: {sched.percentile(50):6.0f} / "
          f"{sched.percentile(95):6.0f} / {sched.percentile(99):6.0f} us")
    print(f"  row-cache hit rate:  {store.row_hit_rate:.3f}")
    print(f"  pooled hit rate:     {store.pooled_hit_rate:.3f}")
    print(f"  inflight IOs (now):  {sched.inflight}  deferred: {sched.deferred}")
    print(f"  feasible QPS (p95):  {sched.qps_at_latency():.0f}")
    print(f"  device engine:       hit rate {engine.hit_rate:.3f}, "
          f"max |pooled - ref| = {max_dev_err:.2e}")

    # warehouse-scale power statement (Table 8 methodology)
    w = Workload("m1", sm_tables=50, avg_pool=42, row_bytes=59,
                 cache_hit_rate=max(store.row_hit_rate, 0.9),
                 total_qps=240 * 1200)
    base = run_scenario("HW-L", HW_L, w, use_sdm=False, qps_override=240)
    sdm = run_scenario("HW-SS+SDM", HW_SS, w, use_sdm=True)
    print(f"  fleet power: HW-L={base.total_power:.0f} -> "
          f"HW-SS+SDM={sdm.total_power:.0f} "
          f"(saving {1 - sdm.total_power/base.total_power:.1%}, paper: 20%)")


if __name__ == "__main__":
    main()
