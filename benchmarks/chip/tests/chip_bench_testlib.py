"""Shared set-up of the chip benchmark's CPU tests: a tiny cell at dlrm-m1
row width, run with the Pallas kernels interpreted on the CPU."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

TESTS = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(TESTS)
ROOT = os.path.dirname(os.path.dirname(CHIP))
DATA = os.path.join(TESTS, "data")
for p in (os.path.join(ROOT, "src"), CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = os.path.join(DATA, "configs", "tiny.json")


def tiny_bench(config_file: str = TINY) -> dict:
    """A BENCHMARK.json-shaped dict of tiny cells, one per test mix."""
    cells = [("tiny.steady", "steady"), ("tiny.backlog", "backlog"),
             ("tiny.drift_backlog", "drift_backlog")]
    return {
        "configs": [{"name": "tiny", "file": config_file}],
        "workloads": [{"name": n, "config": "tiny", "traffic": t,
                       "chips": 1} for n, t in cells],
        "end_to_end": [
            {"name": "query_p50_ms", "unit": "ms", "workloads": ["tiny.steady"]},
            {"name": "query_p95_ms", "unit": "ms", "workloads": ["tiny.steady"]},
            {"name": "queries_per_s", "unit": "queries/s"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "cache_hit_rate", "unit": "%", "moves": "query_p95_ms",
             "workloads": ["tiny.steady"]},
            {"name": "sm_ios_per_query.sat", "unit": "ios/query",
             "moves": "queries_per_s", "workloads": ["tiny.backlog"]},
            {"name": "step_mfu", "unit": "%", "moves": "query_p95_ms",
             "workloads": ["tiny.steady"]}],
    }


def on_cpu(monkeypatch):
    """Let the harness run on the CPU: its look for a TPU, the peaks of
    the CPU device and the persistent compile cache are stubbed out."""
    import jax
    import run
    monkeypatch.setattr(run, "accelerators",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setitem(run.peaks_mod.PEAKS, jax.devices()[0].device_kind,
                        {"hbm_bytes_per_s": 1e11, "flops_per_s": 1e12})
    monkeypatch.setattr(run, "use_compile_cache", lambda: "off")
    return run
