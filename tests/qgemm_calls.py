"""The ``qgemm`` calls of a committed plan's int8 ``run_batch`` program,
read off its jaxpr (tracing only, nothing runs): the kernel tests check the
blocks each call gets, the compile tests the grid steps a dispatch takes."""
import math
import pathlib
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.api import Plan
from repro.core import CompiledSplitExecutor, quantize_model
from repro.models import mobilenet_v2_paper, mobilenet_v3_large

CONFIGS = pathlib.Path(__file__).resolve().parent.parent / "bench" / "configs"
# the benchmark's committed plans, with the model each is for
PLANS = {"mnv3l": (mobilenet_v3_large, "mnv3l_224_int8_spatial.plan.json"),
         "spatial": (mobilenet_v2_paper, "mnv2_112_int8_spatial.plan.json"),
         "neuron": (mobilenet_v2_paper, "mnv2_112_int8_neuron.plan.json")}


class QGemmCall(NamedTuple):
    m: int          # rows of the GEMM, the vmap batch folded in
    k: int
    n: int
    grid: tuple     # the kernel's grid


def eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs in its parameters."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from eqns(sub)


def qgemm_calls(plan_name: str, batch: int) -> list[QGemmCall]:
    """Every ``qgemm`` call of a committed plan's int8 program vmapped over
    ``batch`` samples, with the grid of the one kernel it runs."""
    make, plan_file = PLANS[plan_name]
    model = make()
    plan = Plan.from_json(CONFIGS / plan_file, model).split
    qm = quantize_model(model, [0.05] * (len(model.layers) + 1))
    ex = CompiledSplitExecutor(plan, qm, use_pallas=True, interpret=True)
    x = jax.ShapeDtypeStruct((batch, *model.input_shape), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.vmap(ex._build("int8")))(x)
    calls = []
    for e in eqns(jaxpr.jaxpr):
        if e.params.get("name") != "qgemm" or "jaxpr" not in e.params:
            continue
        xs, ws = (v.aval.shape for v in e.invars[:2])
        grids = [p.params["grid_mapping"].grid
                 for p in eqns(e.params["jaxpr"].jaxpr)
                 if p.primitive.name == "pallas_call"]
        assert len(grids) == 1, grids
        calls.append(QGemmCall(math.prod(xs[:-1]), xs[-1], ws[1], grids[0]))
    return calls
