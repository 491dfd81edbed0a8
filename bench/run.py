"""Run one benchmark cell once on the chip and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``): the configuration's graph is drawn from
the seed, the program's session is built with ``flip.compile`` under the
plan that resolves by default, and one call of the cell's own shape is
run to warm it. Then the window: a closed loop of ``query`` calls for
``--seconds`` seconds, each call's answer on the host before the next is
sent. After it the device's peak memory is read, the program's state is
freed, and a sample of the window's answers drawn from the seed is
compared with the plain reference (``bench.reference``). With
``--trace 1`` the window runs under the profiler and with the program's
per-step telemetry, and the per-layer metrics are reported instead of
the end-to-end ones.

The last lines on standard error are the numbers compared, each beside
its limit; the last line on standard output is the result, one JSON
object. Without a TPU, or with fewer chips than the cell asks for, or on
a device kind missing from ``bench/peaks.json``, the run exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse      # noqa: E402
import contextlib    # noqa: E402
import dataclasses   # noqa: E402
import gc            # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
import tempfile      # noqa: E402
import traceback     # noqa: E402

import numpy as np   # noqa: E402

from bench import graphs, load, spec  # noqa: E402

# rows of per-step telemetry a traced call may record: far above the
# longest fixpoint of any cell (Graph500 SSSP takes about 17 steps,
# PageRank to its 1e-9 residual 75)
TRACE_CAP = 1 << 14
# traversals of the window compared with the reference, drawn from the
# seed; a batched call is compared whole, every row
CHECK_TRAVERSALS = 16
# jaxpr tracing, lowering to MLIR, and the backend compile (or, on a
# persistent-cache hit, the cache read): together, a program's compile
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(**fields) -> None:
    print(json.dumps(fields, default=float), file=sys.stderr, flush=True)


class CompileClock:
    """Compile spans that JAX reports, on the host's `time.time` clock."""

    def __init__(self):
        import jax
        self.spans: list[tuple[float, float]] = []
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def _on_span(self, event, start, end, **_):
        if event in COMPILE_EVENTS:
            self.spans.append((start, end))

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_time_span_listener(self._on_span)

    def between(self, since: float, until: float) -> tuple[int, float]:
        """(events, seconds) of compiling inside [since, until]; nested
        spans overlap, so the seconds are those of their union."""
        inside = sorted((s, e) for s, e in self.spans
                        if e > since and s < until)
        total, reach = 0.0, since
        for s, e in inside:
            e = min(e, until)
            if e > reach:
                total += e - max(s, reach)
                reach = e
        return len(inside), total


def require_chip(chips: int, root: str):
    """JAX's devices and the peak table's row for them, or exit."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX's first device is "
                         f"{devs[0].platform}: {devs[0].device_kind}); "
                         "the benchmark measures the chip and never runs "
                         "on the CPU")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    peaks = spec.load_json(os.path.join(root, "bench", "peaks.json"))
    kind = devs[0].device_kind
    if kind not in peaks:
        raise SystemExit(f"bench: device kind {kind!r} is not in "
                         f"bench/peaks.json ({sorted(peaks)})")
    return devs[:chips], peaks[kind]


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: `$JAX_COMPILATION_CACHE_DIR`
    where set, else the fixed `<checkout>/.jax_cache`. Every program is
    written, however small or quick, so later runs compile nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


@contextlib.contextmanager
def annotate(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


# ------------------------------------------------------------------ #
# what a traversal covers: its root's component
# ------------------------------------------------------------------ #
class Components:
    """Per-component sizes, for the work a traversal needs."""

    def __init__(self, csr: graphs.CSR):
        self.label = graphs.components(csr)
        u = csr.sources()
        self.half_edges = np.bincount(self.label[u], minlength=csr.n)
        self.vertices = np.bincount(self.label, minlength=csr.n)
        self.total = (int(csr.m), int(csr.n))

    def of(self, src, whole: bool) -> tuple[int, int]:
        """(half-edges, vertices) a traversal from `src` covers."""
        if whole:
            return self.total
        lab = self.label[src]
        return int(self.half_edges[lab]), int(self.vertices[lab])

    def union_half_edges(self, srcs, whole: bool) -> int:
        if whole:
            return self.total[0]
        return int(self.half_edges[np.unique(self.label[srcs])].sum())


# ------------------------------------------------------------------ #
# systems under test: the program, or the reference in its place
# ------------------------------------------------------------------ #
@dataclasses.dataclass
class Answer:
    attrs: np.ndarray               # (n,) or (B, n)
    steps: np.ndarray               # (B,) fixpoint steps per row
    converged: bool
    weight_bytes: int | None = None  # telemetry's weight-stream bytes


class ProgramSystem:
    """`flip.compile(graph, program)` under the default plan."""

    def __init__(self, csr: graphs.CSR, program: str):
        from repro import api as flip
        from repro.graphs.csr import Graph
        import jax
        g = Graph(indptr=csr.indptr, indices=csr.indices,
                  weights=csr.weights, directed=False)
        self.cq = flip.compile(g, program)
        bg = self.cq.engine.bg
        jax.block_until_ready((bg.blocks, bg.blocks_ext, bg.bsrc, bg.bdst))
        plan = self.cq.plan
        self.plan = {"relax_mode": plan.relax_mode, "compact": plan.compact,
                     "tile": plan.tile, "blocks": int(bg.bsrc.shape[0])}

    def query(self, srcs, traced: bool) -> Answer:
        r = self.cq.query(srcs, trace=TRACE_CAP if traced else False)
        wb = None
        if r.telemetry is not None:
            wb = r.telemetry.summary()["hbm_weight_bytes_est"]
        return Answer(attrs=np.asarray(r.attrs),
                      steps=np.atleast_1d(np.asarray(r.steps)),
                      converged=r.all_converged, weight_bytes=wb)

    def close(self) -> None:
        del self.cq
        gc.collect()


class ReferenceSystem:
    """The plain reference put in the program's place, in `dtype`: the
    control that the comparison has to fail."""

    def __init__(self, csr: graphs.CSR, program: str, dtype):
        self.csr, self.dtype = csr, dtype
        self.ref = spec.program_reference(program)
        self.plan = {"reference_dtype": np.dtype(dtype).name}

    def query(self, srcs, traced: bool) -> Answer:
        rows = [np.asarray(self.ref.solve(self.csr, int(s), self.dtype),
                           dtype=np.float64)
                for s in np.atleast_1d(srcs)]
        attrs = np.stack(rows) if np.ndim(srcs) else rows[0]
        return Answer(attrs=attrs, steps=np.zeros(len(rows), np.int64),
                      converged=True)

    def close(self) -> None:
        pass


# ------------------------------------------------------------------ #
# the window and what the metric readers see
# ------------------------------------------------------------------ #
@dataclasses.dataclass
class Call:
    srcs: object                    # int, or (B,) array
    rows: int
    wall_s: float
    ok: bool                        # returned and converged
    steps: np.ndarray | None = None
    edges: int = 0                  # undirected edges of the traversals
    least_bytes: int = 0            # bytes the call cannot do without
    weight_bytes: int | None = None
    attrs: np.ndarray | None = None
    error: str | None = None

    @property
    def iterations(self) -> int:
        """Fixpoint steps of the call: its longest row."""
        return int(self.steps.max()) if self.steps is not None else 0


@dataclasses.dataclass
class Window:
    """Everything a metric reader (``bench/metrics/<name>.py``) reads."""
    calls: list
    window_s: float
    setup_s: float
    memory_peak_bytes: int | None
    peaks: dict
    trace: object = None            # bench.trace.Summary, traced runs

    @property
    def done(self) -> list:
        return [c for c in self.calls if c.ok]


def least_bytes(comp: Components, srcs, whole: bool) -> int:
    """Bytes a call must move whatever implements it: each half-edge of
    the reached components read once at its 4 B weight (once for the
    whole batch), and each reached vertex's 4 B state written once per
    row."""
    srcs = np.atleast_1d(srcs)
    state = sum(comp.of(s, whole)[1] for s in srcs)
    return 4 * comp.union_half_edges(srcs, whole) + 4 * state


def run_window(system, calls: list, seconds: float, traced: bool,
               comp: Components, whole: bool) -> tuple[list, float]:
    done: list[Call] = []
    with annotate("bench.window"):
        t0 = time.perf_counter()
        i = 0
        while True:
            srcs = calls[i % len(calls)]
            i += 1
            c0 = time.perf_counter()
            ans, err = None, None
            with annotate("bench.query"):
                try:
                    ans = system.query(srcs, traced)
                except Exception:   # a failed query is counted, not fatal
                    err = traceback.format_exc()
            c1 = time.perf_counter()
            with annotate("bench.record"):
                rows = int(np.size(srcs))
                call = Call(srcs=srcs, rows=rows, wall_s=c1 - c0,
                            ok=ans is not None and ans.converged,
                            error=err)
                if ans is not None:
                    call.steps, call.attrs = ans.steps, ans.attrs
                    call.weight_bytes = ans.weight_bytes
                    call.edges = sum(comp.of(s, whole)[0]
                                     for s in np.atleast_1d(srcs)) // 2
                    call.least_bytes = least_bytes(comp, srcs, whole)
                done.append(call)
            if c1 - t0 >= seconds:
                return done, c1 - t0


# ------------------------------------------------------------------ #
# the comparison with the reference
# ------------------------------------------------------------------ #
def check(calls: list, csr: graphs.CSR, program: str, seed: int):
    """Compare a seeded sample of the window's calls, every row, with the
    reference. Returns ``(numbers, limits, wrong_rows, rows_compared)``;
    each number is the worst over the sample."""
    ref = spec.program_reference(program)
    rng = np.random.default_rng([int(seed), 2])
    order = rng.permutation(len(calls))
    numbers = {k: 0 for k in ref.LIMITS}
    cache: dict[int, np.ndarray] = {}
    rows = wrong = 0
    for i in order:
        if rows >= CHECK_TRAVERSALS:
            break
        c = calls[i]
        if not c.ok:
            continue
        got = np.atleast_2d(c.attrs) if np.ndim(c.srcs) else c.attrs[None]
        for s, row in zip(np.atleast_1d(c.srcs), got):
            s = int(s)
            if s not in cache:
                cache[s] = ref.solve(csr, s)
            got_numbers = ref.compare(row, cache[s])
            for k, v in got_numbers.items():
                numbers[k] = max(numbers[k], v)
            wrong += any(v > ref.LIMITS[k] for k, v in got_numbers.items())
            rows += 1
    return numbers, dict(ref.LIMITS), wrong, rows


# ------------------------------------------------------------------ #
def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = spec.ROOT, require_tpu: bool = True,
             control: bool = False, t_start: float | None = None) -> dict:
    """One run of one cell; returns the result object (see module doc).
    `require_tpu=False` skips the look for a chip (tests on the CPU);
    `control=True` puts the reference, one precision below the
    configuration's, in the program's place."""
    t_start = time.time() if t_start is None else t_start
    cell = spec.cell(workload, root)
    program = cell.traffic["program"]
    whole = not spec.program_reference(program).SOURCED
    import jax
    if require_tpu:
        devs, peaks = require_chip(cell.chips, root)
    else:
        devs, peaks = jax.devices()[:cell.chips], {}
    cache_dir = enable_compile_cache(root)
    clock = CompileClock()
    split = {}

    t = time.time()
    with annotate("bench.setup.graph"):
        csr = graphs.generate(cell.config, seed)
        comp = Components(csr)
        calls = load.calls(cell.traffic, csr, seed)
    split["graph_s"] = time.time() - t
    t = time.time()
    with annotate("bench.setup.compile"):
        if control:
            dtype = spec.control_dtype(cell.config["precision"])
            system = ReferenceSystem(csr, program, dtype)
        else:
            system = ProgramSystem(csr, program)
    split["session_s"] = time.time() - t
    t = time.time()
    with annotate("bench.setup.warm"):
        warm = system.query(calls[0], trace)
    split["warm_s"] = time.time() - t
    split["warm_compiles"], split["warm_compile_s"] = clock.between(
        t, time.time())
    setup_s = time.time() - t_start
    log(setup=dict(split, setup_s=setup_s, plan=system.plan,
                   vertices=csr.n, half_edges=csr.m,
                   warm_steps=warm.steps.tolist(), compile_cache=cache_dir))
    del warm

    profile_dir = None
    if trace:
        from bench import trace as tr
        profile_dir = tempfile.mkdtemp(prefix="bench-trace-")
        tr.start(profile_dir)
    w0 = time.time()
    done, window_s = run_window(system, calls, seconds, trace, comp, whole)
    w1 = time.time()
    if trace:
        tr.stop()
    n_comp, comp_s = clock.between(w0, w1)
    clock.close()
    # host ms per fixpoint step of each call, at 0/10/50/90/100%: a slow
    # run whose calls all slowed differs from one with a few stalled calls
    per_step = [1e3 * c.wall_s / c.iterations for c in done if c.iterations]
    log(window={"seconds": window_s, "calls": len(done),
                "compiles_in_window": n_comp, "compile_s_in_window": comp_s,
                "call_ms_per_step": np.percentile(
                    per_step, [0, 10, 50, 90, 100]).tolist()
                if per_step else None})
    peak = None
    if not control:
        stats = [d.memory_stats() or {} for d in devs]
        vals = [s["peak_bytes_in_use"] for s in stats
                if "peak_bytes_in_use" in s]
        peak = max(vals) if vals else None
    system.close()

    summary = None
    if trace:
        summary = tr.reduce(profile_dir, chips=len(devs))
        shutil.rmtree(profile_dir, ignore_errors=True)
    win = Window(calls=done, window_s=window_s, setup_s=setup_s,
                 memory_peak_bytes=peak, peaks=peaks, trace=summary)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.metric_reader(m["name"], root)(win)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    for c in done:
        if c.error:
            log(query_error=c.error.strip().splitlines()[-1])
    numbers, limits, wrong, checked = check(done, csr, program, seed)
    failed = sum(c.rows for c in done if not c.ok) + wrong
    attempted = sum(c.rows for c in done)
    correct = (failed == 0 and checked > 0
               and all(numbers[k] <= limits[k] for k in limits))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["check"] = {k: {"value": numbers[k], "limit": limits[k]}
                       for k in limits}
    log(compared={"traversals": checked, "wrong": wrong})
    for k, v in result["check"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = os.path.join(spec.ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: the program is missing ({src}/repro); "
                         "run from a checkout of the repository")
    sys.path.insert(0, src)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
