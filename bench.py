"""Placeholder, not a benchmark: the benchmark is `benchmarks/run.py`
(`BENCHMARK.json`, PERF.md §1).

The 4,136-line harness that lived under this name was deleted in PR 48.
The NAME stays because `tests/benchmarks/test_bench_manifest.py` uses it as
its example of a file at the root that lies outside the benchmark's `paths`
(`manifest.validate` takes a command word for a file only if it exists), and
only a `benchmark` PR may edit that test: the first one to do so points the
case at another root file and deletes this one (ROADMAP Design 1 (g)).
"""

if __name__ == "__main__":
    raise SystemExit("no benchmark here: run `python3 benchmarks/run.py "
                     "--workload <cell> ...` (PERF.md §1)")
