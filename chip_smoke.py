"""One-chip bring-up check: MobileNetV2@112 and MobileNetV3-Large@224
through the normal serving path.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py

It drives ``Cluster`` -> ``Planner`` -> ``Plan.compile`` -> ``Session`` ->
``Server`` once, on the paper's model at its published widths (112x112,
width 1.0, random weights from seed 0), and checks what comes out:

1. plan — the planner's choice under the README objective (latency, 512 KB
   per-worker RAM cap on the 8-worker heterogeneous demo cluster), which
   must contain spatial blocks (the banded ``dwconv3x3_bands`` +
   ``im2col_bands`` kernel path), and a plan pinned to ``modes=("neuron",)``
   (the flat ``dwconv3x3`` + ``qgemm_padded`` path);
2. int8 — both plans compiled as int8 Sessions and served as tenants of one
   ``Server``, 16 requests each through ``Server.submit`` (two full buckets
   of 8).  Every ticket must be fulfilled with no rejection or failed
   dispatch; the lowered programs must call the compiled Pallas kernels
   (``tpu_custom_call``); outputs must equal, bit for bit, the same plans
   compiled with ``use_pallas=False`` on this chip and the eager
   ``SplitExecutor`` oracle (one request);
3. MobileNetV3-Large@224 (squeeze-excite gates, hard-swish, 5x5 depthwise)
   on the planner's spatial plan the benchmark commits
   (``bench/configs/mnv3l_224_int8_spatial.plan.json``), served as an int8
   tenant with buckets (1, 32): a full bucket and a single request, bit for
   bit equal to ``use_pallas=False`` and, on two requests, to the eager
   oracle; its lowered program calls the 5x5 kernel (``dwconv5x5_bands``);
4. float — the planner's plan served as a float Session, within
   ``FLOAT_RTOL`` of ``reference_forward`` at ``highest`` matmul precision
   (last: it sets that precision for the process, and the int8 kernels
   refuse an int8 matmul at float32 contract precision).

No phase catches an error and goes on; any failure exits non-zero.  On a
host without a TPU it exits non-zero at once: there is no CPU path.  It
starts no subprocess.  The last line of standard output, printed only when
every phase passed, is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
MAX_BATCH = 8
BUCKETS = (1, MAX_BATCH)
N_REQUESTS = 2 * MAX_BATCH          # per tenant: at least one full bucket
V3_BATCH = 32                       # MobileNetV3-Large: one full bucket
# Float check, as a fraction of the reference's largest |logit|.  Both sides
# run at "highest" matmul precision (a multi-pass bf16 decomposition that is
# about, but not bit-for-bit, f32), and the split executor sums shards and
# bands in another order than the monolithic reference: on the CPU the two
# agree to ~1e-5.  At the TPU's default precision the conv operands are
# rounded to bf16; on this random-weight 53-layer model that rounding,
# emulated on a CPU, compounds to 0.09-0.11 of max|logit| over 4 seeded
# inputs (the chip's own figure is printed below for information).  No
# tolerance could tell that from a wrong answer, so the float tenant is
# served at "highest".
FLOAT_RTOL = 1e-3


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def serve(server, tenants, xs) -> dict[str, np.ndarray]:
    """Submit every request to every tenant, wait for all tickets, and hold
    the server to zero rejections and zero failed dispatches."""
    with server:
        tickets = {name: [(time.perf_counter(), server.submit(name, x))
                          for x in xs] for name in tenants}
        # result() re-raises the error of a ticket whose batch failed
        outs = {name: np.stack([t.result(timeout=600) for _, t in ts])
                for name, ts in tickets.items()}
    for name, ts in tickets.items():
        q = server.stats(name)
        if q.rejected or q.failed or q.completed != len(xs):
            fail(f"{name}: {q.completed}/{len(xs)} completed, "
                 f"{q.rejected} rejected, {q.failed} failed")
        wall = [t.completed_at - t0 for t0, t in ts]
        print(f"  {name}: {len(xs)} requests, wall per request "
              f"min {min(wall):.4f} s, max {max(wall):.4f} s")
    return outs


def main() -> int:
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        fail(f"JAX found no TPU (default backend {backend!r}); this check "
             "runs only on the chip")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.api import Cluster, Objective, Planner
    from repro.compile_cache import use_compile_cache
    from repro.core import SplitExecutor, reference_forward
    from repro.api import Plan
    from repro.models import mobilenet_v2_paper, mobilenet_v3_large
    from repro.serve import SLO, Server

    print(f"compile cache: {use_compile_cache()}")
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    # admission is not under test: every request must be served
    open_slo = SLO(p99_target_s=None, queue_cap=None)

    # -- 1. plan ------------------------------------------------------------
    t0 = time.perf_counter()
    model = mobilenet_v2_paper(seed=0)
    planner = Planner(model, Cluster.heterogeneous_demo(8))
    objective = Objective(minimize="latency", ram_cap_bytes=512 * 1024)
    plans = {"planner": planner.plan(objective),
             "neuron": planner.plan(dataclasses.replace(
                 objective, modes=("neuron",)))}
    for name, p in plans.items():
        n_spatial = sum(sp.mode == "spatial" for sp in p.split.splits)
        print(f"plan {name}: mode={p.mode}/{p.fusion} "
              f"workers={p.n_workers} spatial layers={n_spatial}/"
              f"{len(p.split.splits)} simulated MCU latency "
              f"{p.latency_s:.3f} s")
    if not any(sp.mode == "spatial" for sp in plans["planner"].split.splits):
        fail("the planner's plan has no spatial block: the banded kernel "
             "path would not run")
    print(f"plan phase: {time.perf_counter() - t0:.1f} s")

    rng = np.random.default_rng(0)
    xs = rng.standard_normal((N_REQUESTS, *model.input_shape)).astype(
        np.float32)

    # -- 2. int8 ------------------------------------------------------------
    server = Server()
    sessions, qmodel = {}, None
    for name, p in plans.items():
        t0 = time.perf_counter()
        sess = p.compile(precision="int8", qmodel=qmodel, seed=0,
                         max_batch=MAX_BATCH, buckets=BUCKETS)
        qmodel = sess.qmodel            # one calibration for every plan
        sessions[name] = server.add_tenant(name, sess, slo=open_slo)
        print(f"int8 {name}: compiled buckets {sess.buckets} in "
              f"{time.perf_counter() - t0:.1f} s")
        text = sess.engine.lower_batch(xs[:MAX_BATCH], "int8").as_text()
        if "tpu_custom_call" not in text:
            fail(f"int8 {name}: the lowered program calls no compiled "
                 "Pallas kernel")
    outs = serve(server, plans, xs)

    for name, p in plans.items():
        t0 = time.perf_counter()
        plain = p.compile(precision="int8", qmodel=qmodel, use_pallas=False,
                          max_batch=MAX_BATCH, buckets=(MAX_BATCH,))
        if not np.array_equal(outs[name], plain.submit_many(xs)):
            fail(f"int8 {name}: Pallas path != use_pallas=False path")
        print(f"int8 {name}: bit-exact vs use_pallas=False "
              f"({time.perf_counter() - t0:.1f} s)")
    # int8 output does not depend on the split geometry, so the planner
    # plan's eager oracle pins both tenants
    t0 = time.perf_counter()
    oracle = SplitExecutor(plans["planner"].split, qmodel).run(
        xs[0], mode="int8")
    for name in plans:
        if not np.array_equal(outs[name][0], oracle):
            fail(f"int8 {name}: request 0 != eager SplitExecutor oracle")
    print(f"int8: request 0 bit-exact vs eager oracle "
          f"({time.perf_counter() - t0:.1f} s)")

    # -- 3. MobileNetV3-Large@224 -------------------------------------------
    t0 = time.perf_counter()
    v3 = mobilenet_v3_large(seed=0)
    v3_plan = Plan.from_json(
        ROOT / "bench" / "configs" / "mnv3l_224_int8_spatial.plan.json", v3)
    print(f"plan mnv3l: mode={v3_plan.mode}/{v3_plan.fusion} "
          f"workers={v3_plan.n_workers}")
    v3_xs = rng.standard_normal((V3_BATCH + 1, *v3.input_shape)).astype(
        np.float32)
    v3_sess = v3_plan.compile(precision="int8", seed=0, max_batch=V3_BATCH,
                              buckets=(1, V3_BATCH))
    text = v3_sess.engine.lower_batch(v3_xs[:V3_BATCH], "int8").as_text()
    if "tpu_custom_call" not in text or "dwconv5x5_bands" not in text:
        fail("int8 mnv3l: the lowered program calls no compiled 5x5 kernel")
    print(f"int8 mnv3l: compiled buckets {v3_sess.buckets} in "
          f"{time.perf_counter() - t0:.1f} s")
    v3_server = Server()
    v3_server.add_tenant("mnv3l", v3_sess, slo=open_slo)
    v3_out = serve(v3_server, ["mnv3l"], v3_xs[:V3_BATCH])["mnv3l"]
    v3_one = v3_sess.run(v3_xs[V3_BATCH])
    t0 = time.perf_counter()
    v3_plain = v3_plan.compile(precision="int8", qmodel=v3_sess.qmodel,
                               use_pallas=False, max_batch=V3_BATCH,
                               buckets=(1, V3_BATCH))
    if not (np.array_equal(v3_out, v3_plain.submit_many(v3_xs[:V3_BATCH]))
            and np.array_equal(v3_one, v3_plain.run(v3_xs[V3_BATCH]))):
        fail("int8 mnv3l: Pallas path != use_pallas=False path")
    print(f"int8 mnv3l: buckets 32 and 1 bit-exact vs use_pallas=False "
          f"({time.perf_counter() - t0:.1f} s)")
    t0 = time.perf_counter()
    v3_oracle = SplitExecutor(v3_plan.split, v3_sess.qmodel)
    for i in range(2):
        if not np.array_equal(v3_out[i], v3_oracle.run(v3_xs[i], "int8")):
            fail(f"int8 mnv3l: request {i} != eager SplitExecutor oracle")
    print(f"int8 mnv3l: requests 0-1 bit-exact vs eager oracle "
          f"({time.perf_counter() - t0:.1f} s)")

    # -- 4. float -----------------------------------------------------------
    with jax.default_matmul_precision("highest"):
        ref = np.stack([reference_forward(model, x) for x in xs])
    scale = float(np.abs(ref).max())
    default_ref = np.stack([reference_forward(model, x) for x in xs])
    print(f"float: default-precision reference differs from highest by "
          f"{np.abs(default_ref - ref).max() / scale:.3g} of max|logit|")
    jax.config.update("jax_default_matmul_precision", "highest")
    t0 = time.perf_counter()
    fserver = Server()
    fserver.add_tenant("planner-float", plans["planner"].compile(
        precision="float", max_batch=MAX_BATCH, buckets=BUCKETS),
        slo=open_slo)
    print(f"float planner: compiled in {time.perf_counter() - t0:.1f} s")
    fout = serve(fserver, ["planner-float"], xs)["planner-float"]
    err = float(np.abs(fout - ref).max())
    print(f"float: max|served - reference| = {err:.3g} "
          f"({err / scale:.3g} of max|logit| {scale:.3g}; "
          f"limit {FLOAT_RTOL:g})")
    if not err <= FLOAT_RTOL * scale:
        fail("float output outside its tolerance")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
