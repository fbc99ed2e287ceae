#!/usr/bin/env python3
"""perfbench/run.py — one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, JAX touched once. Everything that belongs to one cell is
data, found by the names in BENCHMARK.json (see perfbench/README.md):

  the cell            BENCHMARK.json "workloads"
  its configuration   the "file" of its entry in "configs"
  its traffic mix     perfbench/traffic/<traffic>.json; its "driver" names
                      the module perfbench/drivers/<driver>.py
  per-layer metrics   perfbench/layer_metrics/<name>.json; its "reader"
                      names the module perfbench/readers/<reader>.py

The last stdout line is one JSON object with the keys correct, attempted,
failed, metrics, device (and breakdown with --trace 1) and, last, compared:
each number the comparison with the plain reference read, beside its
limit. The same numbers are the last lines on stderr. With --trace 0 the
metrics are the cell's end-to-end metrics, with --trace 1 its per-layer
metrics. Any platform but a TPU is refused (exit 1, no result) unless the
configuration is marked as a rehearsal.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: how much of a traced run's window the profiler wraps, and how far in
TRACE_AFTER_S = 1.0
TRACE_FOR_S = 3.0


class Refused(Exception):
    """The run cannot be made here: exit 1, no result line."""


def say(msg: str) -> None:
    """A labelled detail line, with the seconds since the process began."""
    print(f"[perfbench +{time.perf_counter() - T_PROCESS_START:.1f}s] {msg}",
          flush=True)


# ------------------------------------------------------------ finding things


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str) -> dict:
    """The cell with everything its names lead to."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(
            f"no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})"
        )
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(root / "perfbench" / "traffic" / f"{cell['traffic']}.json")
    return {
        "bench": bench,
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": [
            m for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])
        ],
        "per_layer": [
            m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
        ],
    }


def load_layer_metrics(root: Path) -> dict[str, dict]:
    """Every perfbench/layer_metrics/*.json, by metric name."""
    out = {}
    for path in sorted((root / "perfbench" / "layer_metrics").glob("*.json")):
        spec = load_json(path)
        out[spec["name"]] = spec
    return out


def module(kind: str, name: str):
    """perfbench/<kind>/<name>.py, e.g. a driver or a reader."""
    return importlib.import_module(f"perfbench.{kind}.{name}")


# ------------------------------------------------------------------ the window


class Window:
    """The measured window of one run, shared by every driver: it keeps
    the clock, counts the events, and in a traced run starts and stops
    the profiler on event boundaries, so that the traced part holds a
    whole number of events."""

    def __init__(self, seconds: float, trace_dir: Path | None):
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.events = 0
        self.t0 = self.t1 = 0.0
        self._tracing = False
        self._annotation = None
        self.traced_events = 0
        self._trace_done = trace_dir is None

    def open(self) -> None:
        self.t0 = time.perf_counter()

    def more(self) -> bool:
        return time.perf_counter() - self.t0 < self.seconds

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def event_done(self) -> None:
        self.events += 1
        if self._trace_done:
            return
        now = self.elapsed()
        if not self._tracing and now >= TRACE_AFTER_S:
            self._start_trace()
        elif self._tracing:
            self.traced_events += 1
            if now >= TRACE_AFTER_S + TRACE_FOR_S:
                self._stop_trace()

    def close(self) -> None:
        if self._tracing:
            self._stop_trace()
        self.t1 = time.perf_counter()

    @property
    def length_s(self) -> float:
        return self.t1 - self.t0

    def _start_trace(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation("perfbench:window")
        self._annotation.__enter__()
        self._tracing = True

    def _stop_trace(self) -> None:
        import jax

        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._tracing = False
        self._trace_done = True


# -------------------------------------------------------------------- the run


def device_report(jax) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }


def check_tables(obs: dict) -> dict:
    """The comparison that decides `correct`: every table the driver kept
    from the timed path (a seeded sample and the last), against the plain
    reference for the graph as it stood then. Exact, so every limit is 0."""
    from perfbench import compare, reference

    t0 = time.perf_counter()
    uni_bad = mpls_bad = uni_n = mpls_n = 0
    for chk in obs["checks"]:
        want_u, want_m = reference.tables(chk["graph"], chk["root"])
        got_u = compare.plain_unicast(chk["unicast"])
        got_m = compare.plain_mpls(chk["mpls"])
        nu, eg_u = compare.count_differences(got_u, want_u)
        nm, eg_m = compare.count_differences(got_m, want_m)
        if nu or nm:
            say(
                f"check {chk['label']}: {nu} unicast routes differ {eg_u}, "
                f"{nm} mpls routes differ {eg_m}"
            )
        uni_bad += nu
        mpls_bad += nm
        uni_n += len(want_u)
        mpls_n += len(want_m)
    say(
        f"compared {len(obs['checks'])} tables ({uni_n} unicast, {mpls_n} mpls "
        f"routes) with the reference in {time.perf_counter() - t0:.1f}s"
    )
    return {
        "unicast_routes_differ": {"value": uni_bad, "limit": 0},
        "mpls_routes_differ": {"value": mpls_bad, "limit": 0},
        # the least that has to have been compared: one table, non-empty
        "tables_missing": {
            "value": int(not obs["checks"] or not uni_n or not mpls_n),
            "limit": 0,
        },
        "events_failed": {"value": int(obs["failed"]), "limit": 0},
    }


def run_cell(
    root: Path, workload: str, seed: int, seconds: float, trace: bool,
    t_start: float,
) -> tuple[int, dict | None]:
    """Everything after the process-level set-up; returns (exit code,
    result line). Re-entrant: the tests call it in-process."""
    import jax

    from perfbench.stats import percentile, statistic

    found = load_cell(root, workload)
    cell, config, traffic = found["cell"], found["config"], found["traffic"]
    dev0 = jax.devices()[0]
    if dev0.platform != "tpu" and not config.get("rehearsal"):
        raise Refused(
            f"jax found platform {dev0.platform!r} ({dev0.device_kind}), not "
            f"a TPU; only a configuration marked as a rehearsal runs there"
        )
    if len(jax.devices()) < cell["chips"]:
        raise Refused(
            f"cell {workload} asks for {cell['chips']} chips, jax found "
            f"{len(jax.devices())}"
        )
    say(
        f"cell {workload} seed {seed} seconds {seconds} trace {int(trace)} "
        f"on {dev0.platform} {dev0.device_kind} x{len(jax.devices())}"
    )

    from perfbench.meter import Meter

    trace_dir = None
    if trace:
        # one trace per cell stays on disk until that cell's next traced
        # run replaces it: nothing more is written by keeping it
        trace_dir = root / ".perfbench_trace" / workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    window = Window(seconds, trace_dir)
    ctx = {
        "config": config,
        "traffic": traffic,
        "seed": seed,
        "window": window,
        "meter": Meter(),
        "say": say,
    }
    obs = module("drivers", traffic["driver"]).run(ctx)
    setup_s = (window.t0 - t_start)
    obs["window_s"] = window.length_s
    obs["events"] = window.events
    device = device_report(jax)
    say(
        f"window {obs['window_s']:.2f}s, {obs['events']} events, "
        f"{obs['failed']} failed, set-up {setup_s:.1f}s"
    )

    for name, values in obs["series"].items():
        # drift inside the window shows here: the median of each quarter
        if name == "latency_ms" and len(values) >= 8:
            q = len(values) // 4
            say("latency_ms median by quarter of the window: " + ", ".join(
                f"{percentile(values[i * q:(i + 1) * q], 0.5):.2f}"
                for i in range(4)))

    metrics: dict[str, dict] = {}
    traced: dict = {}
    if not trace:
        for m in found["end_to_end"]:
            if m["name"] == "setup_s":
                value = setup_s
            else:
                how = traffic["end_to_end"][m["name"]]
                value = statistic(
                    how["stat"], obs["series"].get(how.get("series"), []), obs
                )
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        from perfbench import trace_reduce

        reduced = trace_reduce.reduce_dir(trace_dir)
        obs["trace"] = reduced
        obs["traced_events"] = window.traced_events
        obs["device_kind"] = dev0.device_kind
        specs = load_layer_metrics(root)
        for m in found["per_layer"]:
            spec = specs.get(m["name"])
            if spec is None:
                raise Refused(
                    f"per-layer metric {m['name']} has no "
                    f"perfbench/layer_metrics/{m['name']}.json"
                )
            value = module("readers", spec["reader"]).read(
                obs, spec.get("args", {})
            )
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        traced["breakdown"] = {
            "device_ops": reduced["device_ops"][:10],
            "idle_gaps": reduced["idle_gaps"][:10],
        }
        say(
            f"trace: {window.traced_events} events in "
            f"{reduced['window_s']:.3f}s, device busy {reduced['busy_s']:.3f}s"
        )

    compared = check_tables(obs)
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    result = {
        "correct": correct,
        "attempted": int(obs["attempted"]),
        "failed": int(obs["failed"]),
        "metrics": metrics,
        "device": device,
        **traced,
        "compared": compared,
    }
    for name, c in compared.items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    return 0, result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        if not (ROOT / "openr_tpu").is_dir():
            raise Refused(
                f"{ROOT} holds no openr_tpu/: the benchmark measures the "
                "program beside it and is nothing alone"
            )
        # one fixed compile cache inside the checkout (the program places
        # the same one); every program, however small, is kept there, so a
        # second run of a cell compiles nothing
        os.environ.setdefault(
            "JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache")
        )
        logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
        import jax

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        rc, result = run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            T_PROCESS_START,
        )
    except Refused as exc:
        print(f"perfbench: refused: {exc}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
