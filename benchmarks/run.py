# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

Each module reproduces one paper table/figure; the roofline benchmark (slow:
it compiles shallow-unrolled probes per cell) runs only with --roofline.

``--json PATH`` additionally writes every executed suite's returned dict to
a machine-readable JSON file (``make bench-json`` -> ``BENCH_serve.json``).
Entries are keyed by ``(git_sha, generated_unix)`` and APPENDED — the file
accumulates the perf trajectory (us/query for ``serve_batched``,
``perf_trace`` and the scenario sweep) across PRs instead of overwriting it.
"""
import argparse
import json
import os
import subprocess
import sys
import time
import traceback


def _git_sha() -> str:
    """Short HEAD sha of the repo this file lives in.

    Runs ``git -C <repo root>`` (the previous cwd-based form recorded
    "unknown" whenever the benchmarks dir wasn't itself the work tree);
    when the git binary is missing or refuses (ownership checks in CI
    sandboxes), falls back to reading ``.git/HEAD``/refs directly."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        r = subprocess.run(["git", "-C", root, "rev-parse", "--short", "HEAD"],
                           capture_output=True, text=True, timeout=10)
        if r.returncode == 0 and r.stdout.strip():
            return r.stdout.strip()
    except Exception:  # noqa: BLE001
        pass
    try:
        with open(os.path.join(root, ".git", "HEAD")) as f:
            head = f.read().strip()
        if head.startswith("ref:"):
            ref = head.split(None, 1)[1]
            ref_path = os.path.join(root, ".git", *ref.split("/"))
            if os.path.exists(ref_path):
                with open(ref_path) as f:
                    return f.read().strip()[:7]
            with open(os.path.join(root, ".git", "packed-refs")) as f:
                for line in f:
                    if line.strip().endswith(ref):
                        return line.split()[0][:7]
        elif head:
            return head[:7]
    except OSError:
        pass
    return "unknown"


def _append_json(path: str, results: dict) -> None:
    """Append a (git_sha, generated_unix)-keyed entry, migrating the legacy
    single-snapshot layout ({generated_unix, results}) into the first entry.

    Same-sha re-runs collapse into one entry — suite results are merged so
    a ``--only`` subset run updates its suites without discarding the rest
    of the commit's numbers. "unknown" shas are never collapsed (they may
    be different commits)."""
    data = {"entries": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
            if isinstance(old, dict) and isinstance(old.get("entries"), list):
                data = old
            elif isinstance(old, dict) and "results" in old:
                data["entries"] = [{
                    "git_sha": old.get("git_sha", "unknown"),
                    "generated_unix": old.get("generated_unix", 0),
                    "results": old["results"]}]
        except (json.JSONDecodeError, OSError):
            pass  # unreadable file: start a fresh trajectory
    sha = _git_sha()
    entry = {"git_sha": sha, "generated_unix": int(time.time()),
             "results": results}
    if sha != "unknown":
        prior = [e for e in data["entries"] if e.get("git_sha") == sha]
        if prior:
            merged = dict(prior[-1].get("results") or {})
            merged.update(results)
            entry["results"] = merged
        data["entries"] = [e for e in data["entries"]
                           if e.get("git_sha") != sha]
    data["entries"].append(entry)
    with open(path, "w") as f:
        json.dump(data, f, indent=2, default=str)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--roofline", action="store_true",
                    help="also run the (slow) per-cell roofline probes")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names to run")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write executed suites' result dicts to PATH")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (depruning, device_tail, fig1_skew, fig3_io,
                            fig45_locality, fig6_cache_org, fleet_ops,
                            integrity_tail, interop_warmup, kernels,
                            perf_trace, scenarios, serve_batched,
                            sharded_serve, table8_power, table9_scaleout,
                            table11_multitenancy, table34_pooled)

    suites = [
        ("serve_batched", serve_batched.run),
        ("perf_trace", perf_trace.run),
        ("fig1_skew", fig1_skew.run),
        ("fig3_io", fig3_io.run),
        ("device_tail", device_tail.run),
        ("fig45_locality", fig45_locality.run),
        ("fig6_cache_org", fig6_cache_org.run),
        ("table34_pooled", table34_pooled.run),
        ("table8_power", table8_power.run),
        ("table9_scaleout", table9_scaleout.run),
        ("table11_multitenancy", table11_multitenancy.run),
        ("fleet_ops", fleet_ops.run),
        ("integrity_tail", integrity_tail.run),
        ("scenarios", scenarios.run),
        ("depruning", depruning.run),
        ("interop_warmup", interop_warmup.run),
        ("kernels", kernels.run),
        ("sharded_serve", sharded_serve.run),
    ]
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - {name for name, _ in suites}
        if unknown:
            raise SystemExit(f"unknown suite(s): {sorted(unknown)}")
    print("name,us_per_call,derived")
    results = {}
    failed = 0
    for name, fn in suites:
        if only and name not in only:
            continue
        try:
            results[name] = fn()
        except Exception:  # noqa: BLE001
            failed += 1
            print(f"{name},0.00,ERROR", file=sys.stdout)
            traceback.print_exc()
    if args.roofline:
        from benchmarks import roofline
        results["roofline"] = roofline.run()
    if args.json:
        _append_json(args.json, results)
        print(f"# appended to {args.json}", file=sys.stderr)
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
