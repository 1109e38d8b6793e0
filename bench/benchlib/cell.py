"""One run of one cell: set-up, the measured window, the check of what the
window served against the plain reference, and the metrics.

The program is driven only through its served entry: ``Plan.from_json`` ->
``Plan.compile(precision="int8", qmodel=...)`` -> ``Server.add_tenant`` ->
``Server.submit`` -> the ticket.  The benchmark makes the weights, the
inputs and the activation scales from the seed; the program quantizes the
weights with its own ``quantize_model``.  The reference and the work counts
are the configuration's, as ``spec.Benchmark.reference`` and ``.work``
resolve them.
"""
from __future__ import annotations

import collections
import gc
import math
import pathlib
import shutil
import sys
import time
import types

import numpy as np

from . import loadgen, reference, spec, trace as tracemod, work

COLLECT_GRACE_S = 60.0          # an answer may come this long after close
TRACE_DIR = ".bench_cache/trace"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoDevice("JAX found no accelerator (platform cpu)")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class CompileCounter:
    """Counts XLA compilations (and persistent-cache hits) from JAX's own
    monitoring events, so a compile inside the window shows."""

    def __init__(self, jax):
        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _pool(cfg: dict, seed: int, n: int) -> tuple[np.ndarray, np.ndarray]:
    """Request inputs and calibration inputs from the seed (two streams)."""
    shape = tuple(cfg["input_shape"])
    pool = np.random.default_rng([seed, 1]).standard_normal(
        (n, *shape), dtype=np.float32)
    calib = np.random.default_rng([seed, 2]).standard_normal(
        (int(cfg["calibration_samples"]), *shape), dtype=np.float32)
    return pool, calib


def _stats(session) -> dict:
    s = session.stats()
    return {"requests": s.requests, "batches": s.batches, "padded": s.padded}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def _breakdown(tr: tracemod.Trace, top: int = 10) -> dict:
    """The device ops that took most time in the window, and the longest
    idle gaps, each labelled with the host span that overlaps it most."""
    by_op = collections.Counter()
    for e in tr.ops():
        lo, hi = max(e.start_ns, tr.lo), min(e.end_ns, tr.hi)
        if hi > lo:
            by_op[e.name] += (hi - lo) * 1e-9
    gaps = sorted(((e - s, s, e) for d in tr.devices
                   for s, e in tracemod.gaps(tr.busy(d), tr.lo, tr.hi)),
                  reverse=True)[:top]
    host = [h for h in tr.host_events() if h.dur_ns < tr.hi - tr.lo]
    labelled = []
    for length, s, e in gaps:
        best, label = 0.0, "no host span"
        for h in host:
            ov = min(h.end_ns, e) - max(h.start_ns, s)
            if ov > best:
                best, label = ov, h.name
        labelled.append([label, length * 1e-9])
    return {"device_ops": [[k[:120], v] for k, v in by_op.most_common(top)],
            "idle_gaps": labelled}


def setup(bm: spec.Benchmark, cell_name: str, seed: int, *,
          check_device: bool = True, log=None) -> types.SimpleNamespace:
    """Everything before the first request: weights, inputs and scales from
    the seed, the plan, the program's quantization, and a ``Server`` whose
    one tenant has compiled every bucket the cell's traffic uses."""
    import jax
    from repro.api import Plan
    from repro.core.quantize import quantize_model
    from repro.serve import SLO, Server

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = bm.cell(cell_name)
    cfg, traffic = cell.config, cell.traffic
    device = (device_info(jax, cell.chips) if check_device else
              {"platform": jax.devices()[0].platform,
               "kind": jax.devices()[0].device_kind, "count": 1})
    log(f"device: {device['platform']} {device['kind']} x{device['count']}")
    counter = CompileCounter(jax)
    t = [time.perf_counter()]

    def phase(name):
        now = time.perf_counter()
        log(f"set-up {name}: {now - t[0]:.3f} s ({counter.compiles} "
            f"compiles so far, {counter.cache_hits} cache hits)")
        t[0] = now

    arch = bm.arch(cfg)
    ref = bm.reference(cfg)
    layers = arch.layers(cfg)
    params = arch.make_params(cfg, seed)
    pool, calib = _pool(cfg, seed, int(traffic.get("input_pool", 64)))
    phase("weights and inputs")
    scales = ref.calibrate(layers, params, calib)
    phase("calibration")
    model = spec.program_model(bm.bench, cfg, seed, params=params)
    plan = Plan.from_json(bm.bench / cfg["plan_file"], model)
    sess = plan.compile(precision=cfg["precision"],
                        qmodel=quantize_model(model, scales),
                        max_batch=int(traffic["max_batch"]),
                        buckets=tuple(traffic["buckets"]))
    phase("plan, quantization, session")
    server = Server()
    server.add_tenant(cell.name, sess, slo=SLO(**traffic["slo"]))  # warms
    phase(f"warm-up of buckets {list(sess.buckets)}")
    return types.SimpleNamespace(
        bm=bm, cell=cell, cfg=cfg, traffic=traffic, device=device,
        counter=counter, layers=layers, params=params, pool=pool,
        reference=ref, work=bm.work(cfg), scales=scales, server=server,
        session=sess, log=log, seed=seed)


def window(ctx, seconds: float, traced: bool = False,
           traffic=None) -> types.SimpleNamespace:
    """Drive the traffic (the cell's, or ``traffic`` for a rate sweep) for
    its lead-in and ``seconds``, then collect every answer (for at most
    ``COLLECT_GRACE_S`` past the close)."""
    import jax
    from repro.serve import Overloaded

    traffic = traffic or ctx.traffic
    rng = np.random.default_rng([ctx.seed, 3])
    drive = loadgen.DRIVERS[traffic["arrivals"]]
    annotate = jax.profiler.TraceAnnotation
    trace_dir = pathlib.Path(ctx.bm.root) / TRACE_DIR / ctx.cell.name
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    ctx.server.start()
    try:
        s0 = _stats(ctx.session)
        c0 = ctx.counter.compiles
        with annotate("bench.traffic"):
            t_enter = time.perf_counter()
            reqs, w0, w1 = drive(ctx.server, ctx.cell.name, traffic,
                                 ctx.pool, rng, float(seconds), Overloaded,
                                 annotate)
        compiles = ctx.counter.compiles - c0
        deadline = w1 + COLLECT_GRACE_S
        with annotate("bench.collect"):
            loadgen.collect(reqs, deadline)
        s1 = _stats(ctx.session)
    finally:
        ctx.server.stop(drain=True)
        if traced:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            ctx.log(f"trace written: {time.perf_counter() - t_stop:.3f} s")
    return types.SimpleNamespace(
        requests=reqs, window=(w0, w1), deadline=deadline, seconds=seconds,
        window_requests=[r for r in reqs if w0 <= r.due < w1],
        session=_delta(s0, s1), compiles=compiles, t_enter=t_enter,
        trace_dir=trace_dir if traced else None)


def compare(ref, layers, params, scales, pool, answers, limit) -> dict:
    """The check that decides ``correct``: every answer served in the
    window against the integer reference ``ref`` of its input.  With no
    answer there is nothing to compare, and the gap reads None."""
    if not answers:
        return {"logit_gap_lsb": [None, limit]}
    q = ref.quantize(layers, params, scales, 127)
    used = sorted({p for p, _ in answers})
    want = dict(zip(used, ref.int_forward(layers, q, pool[used])))
    gap = reference.logit_gap_lsb(np.stack([a for _, a in answers]),
                                  q["out_scale"],
                                  np.stack([want[p] for p, _ in answers]),
                                  q["out_scale"])
    return {"logit_gap_lsb": [gap, limit]}


def run(bm: spec.Benchmark, cell_name: str, seed: int, seconds: float,
        traced: bool, t_process: float, *, check_device: bool = True,
        on_ready=None, log=None) -> dict:
    """Run the cell once; returns the result line's object.  ``on_ready``
    (tests only) is called with the server and session once set-up is done.
    """
    import jax
    from repro.core.executor import CompiledSplitExecutor

    ctx = setup(bm, cell_name, seed, check_device=check_device, log=log)
    log = ctx.log
    if on_ready is not None:
        on_ready(ctx.server, ctx.session)
    c = ctx.counter
    log(f"set-up before load: {time.perf_counter() - t_process:.3f} s, "
        f"{c.compiles} compiles ({c.compile_s:.3f} s), "
        f"{c.cache_hits} persistent-cache hits")
    rec = window(ctx, seconds, traced)
    log(f"compiles inside the window: {rec.compiles}")
    mem = jax.devices()[0].memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))

    # free the program's state, then check against the reference
    answers = [(r.pool, np.asarray(r.output).reshape(-1))
               for r in rec.window_requests if r.status == "ok"]
    ctx.server = ctx.session = None
    CompiledSplitExecutor.cache_clear()
    gc.collect()
    t_ref = time.perf_counter()
    checks = compare(ctx.reference, ctx.layers, ctx.params, ctx.scales,
                     ctx.pool, answers, ctx.cfg["correct"]["logit_gap_lsb"])
    unanswered = sum(r.status in ("missing", "error")
                     for r in rec.window_requests)
    checks["unanswered"] = [unanswered, 0]
    gap, limit = checks["logit_gap_lsb"]
    correct = gap is not None and gap <= limit and unanswered == 0
    log(f"reference check: {len(answers)} answers in "
        f"{time.perf_counter() - t_ref:.3f} s")

    rec.__dict__.update(cell=ctx.cell, cfg=ctx.cfg, traffic=ctx.traffic,
                        layers=ctx.layers, work=ctx.work,
                        setup_s=rec.window[0] - t_process,
                        peak=(work.peaks(ctx.device["kind"])
                              if ctx.device["platform"] != "cpu" else None),
                        trace=None)
    device = dict(ctx.device, memory_peak_bytes=memory_peak)
    if traced:
        t_load = time.perf_counter()
        events = tracemod.load_dir(rec.trace_dir)
        log(f"trace read: {len(events)} events in "
            f"{time.perf_counter() - t_load:.3f} s")
        span = [e for e in events if e.name == "bench.traffic"]
        if not span:
            raise RuntimeError("the trace holds no bench.traffic span")
        w0, w1 = rec.window
        lo = span[0].start_ns + (w0 - rec.t_enter) * 1e9
        rec.trace = tracemod.Trace(events, lo, lo + (w1 - w0) * 1e9)
        device.update(busy_s=rec.trace.busy_s(), window_s=rec.trace.window_s)
    metrics = {}
    for m in (ctx.cell.per_layer if traced else ctx.cell.end_to_end):
        value = bm.reader(m.reader).read(rec, m.params)
        if value is None or math.isnan(value):
            log(f"metric {m.name}: nothing to read")
            continue
        metrics[m.name] = {"value": value, "unit": m.unit}
    result = {"correct": correct, "attempted": len(rec.window_requests),
              "failed": sum(r.status != "ok" for r in rec.window_requests),
              "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = _breakdown(rec.trace)
        log(f"trace reduced: {time.perf_counter() - t_load:.3f} s after "
            "it was first read")
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v} (limit {lim})")
    return result
