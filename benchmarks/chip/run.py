"""Run one cell of the benchmark once, on the chip this process finds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> [--rehearse]

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``configs/<config>.json``) under a traffic mix (``traffic/<traffic>.json``)
on one chip.  The run builds the weights from the seed, serves the mix
open loop through ``StreamServer``, warms up on the same traffic, measures
for ``--seconds`` seconds, then checks every answered window, and the
carries the server holds for a sample of the streams, against the plain
reference (``references/<reference>.py``, which alone knows the
architecture).  A stateful cell chains each stream's windows through the
reference and compares the carries read back; a stateless one
(``serving.stateful`` false) runs each window alone from the zero carry
and holds that the server keeps no carry.  With ``--trace 0`` it reports the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics from a
profiler trace of the first seconds of the window.  Each metric is a
reader ``metrics/<name>.py``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, in a traced run
``breakdown``, and last ``checks``: each number compared with its limit).
The process exits non-zero, printing no result, when it finds no TPU or
fewer chips than the cell asks for.  ``--rehearse`` runs on whatever JAX
finds (the CPU, Pallas in interpret mode) at the traffic mix's
``rehearsal`` sizes, and never prints a result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OUT_DIR = os.path.join(HERE, ".out")
CACHE_DIR = os.path.join(HERE, ".jax_cache")
TRACE_S = 3.0                 # traced part of the window, at most
MAX_LATE_S = 1.0              # a saturated generator stops this long after close
CARRY_SAMPLE = 1024           # streams whose carries are read back and compared
MISSING = -(1 << 20)          # a carry the server does not hold (no code reads so)


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


class CompiledInWindow(RuntimeError):
    """Something was traced or compiled inside the measured window."""


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(workload: str):
    """(benchmark, cell, configuration, traffic mix, reference module) of
    ``workload``; a reference that breaks its contract fails here."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(os.path.join(ROOT, entry["file"]))
    mix = load_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    import references
    return bench, cell, cfg, mix, references.load(cfg["reference"])


def metric_specs(bench: Dict, cell: str, kind: str) -> List[Dict]:
    """The ``kind`` ("end_to_end" | "per_layer") metrics of ``cell``."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def reader(name: str) -> Callable:
    return importlib.import_module(f"metrics.{name}").read


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devs[0].platform} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs


class CompileCounter:
    """Counts JAX traces and compilations while ``armed``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        self.first: Optional[str] = None
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **kwargs):
        if self.armed and name in self.EVENTS:
            self.count += 1
            if self.first is None:
                import traceback
                self.first = "".join(traceback.format_stack(limit=12))


class GcPauses:
    """Garbage-collector pauses while ``armed``: (generation, seconds)."""

    def __init__(self):
        self.armed = False
        self.pauses: List = []
        self._t = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.armed:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def summary(self) -> Dict:
        return {"collections": len(self.pauses),
                "gen2": sum(1 for g, _ in self.pauses if g == 2),
                "max_pause_ms": 1e3 * max((d for _, d in self.pauses),
                                          default=0.0)}


class Record:
    """Everything one run saw, for the metric readers and the check."""

    def __init__(self, traffic, seconds: float, warmup_s: float, p: int):
        n_cap = int(traffic.rate * (warmup_s + seconds + MAX_LATE_S)) + 2
        self.traffic = traffic
        self.seconds = seconds
        self.warmup_s = warmup_s
        self.t_sub = np.full(n_cap, np.nan)
        self.t_recv = np.full(n_cap, np.nan)
        self.status = np.zeros(n_cap, np.int8)    # 0 none, 1 ok, 2 error
        self.reset = np.zeros(n_cap, bool)
        self.y = np.zeros((n_cap, p), np.float32)
        self.n_sub = 0
        self.t0 = self.t_open = self.t_close = self.t_final = math.nan
        self.setup_s = math.nan
        self.sink: Dict = {}
        self.carry_pos = np.zeros(0, np.int64)   # schedule positions read back
        self.carry_read = None                   # their carries, (K, codes)
        self.carry_held = np.zeros(0, bool)      # whether the server had one
        self.serving: Dict = {}
        self.trace: Optional[Dict] = None
        self.work: Dict = {}
        self.chips = 1
        self.errors: List[str] = []
        self.gc: Dict = {}
        self.phases: Dict = {}

    @property
    def n_cap(self) -> int:
        return len(self.t_sub)

    def due_range(self):
        """Schedule indices due inside the measured window."""
        r = self.traffic.rate
        return (int(math.ceil(self.warmup_s * r)),
                min(self.n_cap, int(math.ceil((self.warmup_s + self.seconds)
                                              * r))))

    def due_abs(self, idx):
        return self.t0 + self.traffic.due(idx)

    def in_window(self, t):
        return (t >= self.t_open) & (t < self.t_close)


def drive(server, traffic, rec: Record, trace_dir: Optional[str],
          counter: CompileCounter) -> None:
    """Serve the traffic open loop: warm-up, measured window, answers."""
    import gc

    import jax

    gc_pauses = GcPauses()
    gc.callbacks.append(gc_pauses)

    annotate = trace_dir is not None
    span = (jax.profiler.TraceAnnotation if annotate
            else (lambda name: contextlib.nullcontext()))
    n_streams = traffic.streams
    order = [int(s) for s in traffic.order]
    rank = [int(r) for r in traffic.rank]
    pool, rate, row = traffic.pool, traffic.rate, traffic.row
    n_end = rec.due_range()[1]
    stop_gen = threading.Event()
    stop_poll = threading.Event()
    done = [0]

    def generate():
        i = 0
        try:
            while i < n_end and not stop_gen.is_set():
                now = time.perf_counter()
                n_due = min(n_end, int((now - rec.t0) * rate) + 1)
                if i >= n_due:
                    wait = rec.t0 + i / rate - now
                    with span("bench.sleep"):
                        time.sleep(min(max(wait, 0.0), 0.002))
                    continue
                while i < n_due and not stop_gen.is_set():
                    s, k = order[i % n_streams], i // n_streams
                    x = pool[row(s, k)]
                    rec.t_sub[i] = time.perf_counter()
                    with span("bench.submit"):
                        server.submit(s, x)
                    i += 1
                    rec.n_sub = i
        except Exception as e:        # the run reports it, nothing hangs
            rec.errors.append(f"submit: {type(e).__name__}: {e}")

    def consume():
        while True:
            try:
                with span("bench.poll"):
                    rows = server.poll(timeout=0.01)
            except Exception as e:    # a compute-thread failure
                rec.errors.append(f"poll: {type(e).__name__}: {e}")
                rows = []
                time.sleep(0.01)
            t = time.perf_counter()
            for r in rows:
                i = r.seq * n_streams + rank[r.stream_id]
                if i >= rec.n_cap or rec.status[i]:
                    rec.errors.append(f"unexpected answer {r.stream_id}/"
                                      f"{r.seq}")
                    continue
                rec.t_recv[i] = t
                rec.reset[i] = r.state_reset
                if r.error is None:
                    rec.status[i] = 1
                    rec.y[i] = r.y
                else:
                    rec.status[i] = 2
                done[0] += 1
            if stop_poll.is_set() and not rows:
                return

    rec.t0 = time.perf_counter() + 0.05
    rec.t_open = rec.t0 + rec.warmup_s
    rec.t_close = rec.t_open + rec.seconds
    gen = threading.Thread(target=generate, name="bench-generator")
    con = threading.Thread(target=consume, name="bench-consumer")
    gen.start()
    con.start()
    try:
        _sleep_until(rec.t_open)
        server.reset_metrics()
        counter.armed = gc_pauses.armed = True
        if annotate:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench.traced"):
                _sleep_until(min(rec.t_open + TRACE_S,
                                 rec.t_close - 0.25))
            jax.profiler.stop_trace()
        _sleep_until(rec.t_close)
        counter.armed = gc_pauses.armed = False
        rec.sink = server.metrics_summary()
        gen.join(timeout=max(0.0, rec.t_close + MAX_LATE_S
                             - time.perf_counter()))
    finally:
        stop_gen.set()
        gen.join(timeout=60)
        deadline = rec.t_close + MAX_LATE_S + rec.traffic.grace_s
        while done[0] < rec.n_sub and time.perf_counter() < deadline:
            time.sleep(0.01)
        rec.t_final = time.perf_counter()
        stop_poll.set()
        con.join(timeout=60)
        gc.callbacks.remove(gc_pauses)
        rec.gc = gc_pauses.summary()
    if gen.is_alive() or con.is_alive():
        rec.errors.append("a load thread did not stop")


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def expected(cfg: Dict, ref, codes, rec: Record):
    """The reference's output codes of every submitted window, in schedule
    order, and its carries of the streams read back (``rec.carry_pos``).
    Stream ``order[r]`` sends windows ``r, r + streams, ...``; on a
    stateful server each continues the carry of the one before, from the
    zero carry, on a stateless one each starts from the zero carry."""
    tr, n = rec.traffic, rec.n_sub
    n_str = tr.streams
    if not cfg["serving"]["stateful"]:
        out, carries = ref.run_chains(
            cfg, codes, lambda i, k: tr.pool[tr.rows(tr.order[i % n_str],
                                                     i // n_str)],
            np.arange(n), np.ones(n, np.int64), block=16384)
        return out[:, 0], carries
    touched = min(n, n_str)
    per = n // n_str + (np.arange(touched) < n % n_str)
    out, carries = ref.run_chains(
        cfg, codes, lambda ids, k: tr.pool[tr.rows(ids, k)],
        tr.order[:touched], per, block=16384, keep=rec.carry_pos)
    i = np.arange(n)
    return out[i % n_str, i // n_str], carries


def compare(rec: Record, served, expect, carry: Dict) -> Dict:
    """The numbers compared with their limits: ``served`` output codes of
    every submitted window against the reference's ``expect``, the
    ``carry`` check (:func:`carry_gap` or :func:`carries_held`), and the
    run's own counts."""
    n = rec.n_sub
    ok = rec.status[:n] == 1
    gap = int(np.abs(served[ok] - expect[ok]).max()) if ok.any() else None
    return {
        "answered_ok": {"value": int(ok.sum()), "limit": 1, "op": ">="},
        "max_gap_lsb": {"value": gap, "limit": 0, "op": "<="},
        **carry,
        "unanswered": {"value": int((rec.status[:n] == 0).sum()),
                       "limit": 0, "op": "<="},
        "state_resets": {"value": int(rec.reset[:n].sum()), "limit": 0,
                         "op": "<="},
    }


def carry_gap(carries, expect_carries) -> Dict:
    """A stateful cell's carry check: the widest gap between the carries
    read back and the reference's, None when none was read."""
    value = (int(np.abs(carries - expect_carries).max())
             if len(expect_carries) else None)
    return {"carry_gap_lsb": {"value": value, "limit": 0, "op": "<="}}


def carries_held(held) -> Dict:
    """A stateless cell's carry check: the sampled streams the server still
    holds a carry for, none."""
    return {"carries_held": {"value": int(np.sum(held)), "limit": 0,
                             "op": "<="}}


def check(cfg: Dict, ref, params, rec: Record, control: bool = False):
    """Compare what the run served with the reference.

    Returns the checks of the program and, with ``control``, those of the
    control: the reference at 4-bit weights put in the program's place
    (holding no carry on a stateless cell) and held to the same checks,
    else None."""
    stateful = cfg["serving"]["stateful"]
    expect, expect_carries = expected(cfg, ref, ref.weight_codes(cfg, params),
                                      rec)
    served = ref.output_codes(cfg, rec.y[:rec.n_sub])
    checks = compare(rec, served, expect,
                     carry_gap(rec.carry_read, expect_carries) if stateful
                     else carries_held(rec.carry_held))
    if not control:
        return checks, None
    ctl, ctl_carries = expected(cfg, ref, ref.control_codes(cfg, params), rec)
    return checks, compare(rec, ctl, expect,
                           carry_gap(ctl_carries, expect_carries) if stateful
                           else carries_held(()))


def holds(c: Dict) -> bool:
    v, lim = c["value"], c["limit"]
    if v is None:
        return False
    return v <= lim if c["op"] == "<=" else v >= lim


def correct(rec: Record, checks: Dict) -> bool:
    return bool(not rec.errors and all(holds(c) for c in checks.values()))


def read_carries(server, rec: Record, seed: int, cfg: Dict, ref) -> None:
    """Read back the carries the server holds for a sample of the streams,
    drawn from the seed among those whose last window was answered, into
    ``rec.carry_pos`` (schedule positions), ``rec.carry_held`` and
    ``rec.carry_read``: one row of ``ref.carry_codes(cfg)`` codes a stream,
    as ``ref.carry_vector`` lays it out.  A carry the server does not hold
    reads as a row of ``MISSING``; one of another length is an error of
    the run."""
    tr, n = rec.traffic, rec.n_sub
    touched = np.arange(min(n, tr.streams))
    last = touched + tr.streams * ((n - 1 - touched) // tr.streams)
    eligible = np.flatnonzero(rec.status[last] == 1)
    rng = np.random.default_rng(np.random.SeedSequence([seed % 2 ** 64, 17]))
    rec.carry_pos = np.sort(rng.choice(
        eligible, min(CARRY_SAMPLE, len(eligible)), replace=False))
    codes = ref.carry_codes(cfg)
    rec.carry_read = np.full((len(rec.carry_pos), codes), MISSING, np.int64)
    rec.carry_held = np.zeros(len(rec.carry_pos), bool)
    for j, r in enumerate(rec.carry_pos):
        st = server.read_stream_state(int(tr.order[r]))
        if st is None:
            continue
        rec.carry_held[j] = True
        row = ref.carry_vector(cfg, st)
        if row.shape != (codes,):
            rec.errors.append(f"carry of stream {int(tr.order[r])}: "
                              f"{row.shape} codes, the reference holds "
                              f"{codes}")
            continue
        rec.carry_read[j] = row


def serve_cell(workload: str, seed: int, seconds: float, trace: bool,
               rehearse: bool = False, mix_overrides: Optional[Dict] = None,
               wrap_server: Optional[Callable] = None) -> Dict:
    """Serve one run of ``workload`` and record it; returns the run's
    parts for :func:`result_of`.  ``mix_overrides`` replaces keys of the
    traffic mix (the knee sweep); ``wrap_server`` may patch the server
    before traffic starts (the fault tests)."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # The benchmark's own cache: no size limit, so no eviction bookkeeping,
    # and every program is kept however fast it compiled.
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [p for p in (os.path.join(ROOT, "src"), HERE)
                    if p not in sys.path]
    import jax

    import loadgen
    import sut
    import work

    phases = {"imports": time.perf_counter() - T_START}
    bench, cell, cfg, mix, ref = cell_spec(workload)
    chips = int(cell["chips"])
    devices = jax.devices() if rehearse else check_devices(chips)
    phases["devices"] = time.perf_counter() - T_START
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()

    params = ref.make_params(cfg, jax.random.key(
        int(np.random.SeedSequence(seed % 2 ** 64).generate_state(1)[0])))
    traffic = loadgen.make_traffic({**mix, **(mix_overrides or {})}, cfg,
                                   seed, rehearse=rehearse)
    phases["weights_traffic"] = time.perf_counter() - T_START
    server = sut.serve(cfg, params, traffic.serving)
    phases["server"] = time.perf_counter() - T_START
    sut.prime(server, traffic.pool[0])
    phases["primed"] = time.perf_counter() - T_START
    if wrap_server is not None:
        wrap_server(server)
    rec = Record(traffic, seconds, traffic.warmup_s,
                 cfg["model"]["out_features"])
    rec.chips = chips
    trace_dir = None
    if trace:
        trace_dir = os.path.join(OUT_DIR, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        drive(server, traffic, rec, trace_dir, counter)
        rec.setup_s = rec.t_open - T_START
        phases["window_open"] = rec.setup_s
        rec.phases = phases
        peaks_mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices[:chips]]
        read_carries(server, rec, seed, cfg, ref)
    finally:
        server.close(abandon=True, timeout=30)
    if counter.count:
        raise CompiledInWindow(
            f"{counter.count} traces or compilations inside the measured "
            f"window; the first at\n{counter.first}")
    batch = rec.sink["batch"] if rec.sink.get("waves") else None
    dev0 = devices[0]
    rec.serving = {"batch": batch,
                   "deadline_s": server.config.deadline_s,
                   "max_streams": server.config.max_streams,
                   "stateful": cfg["serving"]["stateful"]}
    rec.work = {"ops_per_window": ref.ops_per_window(cfg),
                "bytes_per_wave": work.bytes_per_wave(ref, cfg, batch or 0),
                "ops_per_wave": ref.ops_per_window(cfg) * (batch or 0)}
    if dev0.platform == "tpu":         # a CPU rehearsal has no peaks
        rec.work["peaks"] = work.peaks(dev0.device_kind)
    if trace:
        import trace_reduce
        rec.trace = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(trace_dir)))
    return {"bench": bench, "workload": workload, "cfg": cfg, "ref": ref,
            "params": params, "rec": rec, "devices": devices,
            "peaks_mem": peaks_mem, "seed": seed,
            "trace": trace}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, mix_overrides: Optional[Dict] = None,
             wrap_server: Optional[Callable] = None,
             control: bool = False) -> Dict:
    """One run of ``workload``; returns the result object (see the module
    docstring).  ``control`` adds the control's checks to ``info``;
    the other arguments are :func:`serve_cell`'s."""
    run = serve_cell(workload, seed, seconds, trace, rehearse=rehearse,
                     mix_overrides=mix_overrides, wrap_server=wrap_server)
    return result_of(run, control=control)


def result_of(run: Dict, control: bool = False) -> Dict:
    """The result object of a served run (see the module docstring)."""
    rec, cfg, trace = run["rec"], run["cfg"], run["trace"]
    devices, traffic = run["devices"], run["rec"].traffic
    checks, control_checks = check(cfg, run["ref"], run["params"], rec,
                                   control=control)
    bench, workload, dev0 = run["bench"], run["workload"], devices[0]
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for spec in metric_specs(bench, workload, kind):
        value = reader(spec["name"])(rec)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    lo, hi = _submitted_in_window(rec)
    attempted = int(hi - lo)
    failed = int((rec.status[lo:hi] != 1).sum())
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(max(run["peaks_mem"], default=0))}
    result = {"correct": correct(rec, checks),
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        result["breakdown"] = {"device_ops": rec.trace["device_ops"],
                               "idle_gaps": rec.trace["idle_gaps"]}
    result["info"] = {"serving": rec.serving, "errors": rec.errors[:5],
                      "submitted": rec.n_sub, "streams": traffic.streams,
                      "rate_per_s": traffic.rate, "warmup_s": rec.warmup_s,
                      "seed": run["seed"],
                      "control": control_checks and {
                          "correct": correct(rec, control_checks),
                          "checks": control_checks},
                      "gc": rec.gc, "lag": _lag_summary(rec),
                      "setup_phases_s": rec.phases}
    result["checks"] = checks
    return result


def _lag_summary(rec: Record) -> Dict:
    """The generator's worst lag in the window and when it came."""
    lo, hi = rec.due_range()
    lag = rec.t_sub[lo:hi] - rec.due_abs(np.arange(lo, hi))
    if not len(lag) or np.all(np.isnan(lag)):
        return {}
    i = int(np.nanargmax(lag))
    return {"max_ms": float(lag[i] * 1e3),
            "at_s": float(rec.due_abs(lo + i) - rec.t_open),
            "over_20ms": int(np.sum(lag > 0.02))}


def _submitted_in_window(rec: Record):
    """Schedule indices [lo, hi) whose submit call fell in the window."""
    n = rec.n_sub
    inside = np.flatnonzero(rec.in_window(rec.t_sub[:n]))
    if not len(inside):
        return 0, 0
    return int(inside[0]), int(inside[-1]) + 1


def report_checks(checks: Dict) -> str:
    return "\n".join(f"check {k}: {c['value']} (limit {c['op']} "
                     f"{c['limit']})" for k, c in checks.items())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at rehearsal sizes; print no "
                         "result line")
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), rehearse=args.rehearse)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr, flush=True)
        return 3
    except CompiledInWindow as e:
        print(f"run.py: {e}", file=sys.stderr, flush=True)
        return 4
    info = json.dumps(result["info"])
    print(f"run.py: {info}", file=sys.stderr, flush=True)
    if args.rehearse:
        print("rehearsal (no result line): " + json.dumps(
            {k: v for k, v in result.items() if k != "info"}),
            file=sys.stderr, flush=True)
        print(report_checks(result["checks"]), file=sys.stderr, flush=True)
        return 0
    out = {k: v for k, v in result.items() if k != "info"}
    print(json.dumps(out), flush=True)
    print(report_checks(result["checks"]), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
