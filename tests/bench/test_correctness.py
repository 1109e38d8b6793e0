"""The comparison that decides ``correct``: the reference agrees with the
served program, the control (the reference at int4 in the program's place)
and a broken timed path are caught by the committed limit, and off an
accelerator the command prints no result.

The CPU cells run the smoke configuration with the committed cells' limit
(``logit_gap_lsb`` 10), through the whole of ``cell.run``."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from benchtest_util import DATA, REPO, smoke_root  # noqa: F401 — fixture
from benchlib import cell, reference, spec

BENCH = REPO / "bench"
LIMITS = {name: json.loads((BENCH / "configs" / f"{name}.json").read_text())
          ["correct"]["logit_gap_lsb"]
          for name in ("mnv2_112_int8_spatial", "mnv2_112_int8_neuron")}


def _smoke():
    cfg = json.loads((DATA / "mnv2_smoke_int8_spatial.json").read_text())
    arch = spec.load_module(BENCH / "models" / f"{cfg['arch']}.py")
    return cfg, arch, arch.layers(cfg)


@pytest.mark.parametrize("seed", [3, 4, 2**31 + 9])
def test_control_int4_fails_every_limit(seed):
    """The reference at int4 in the program's place reads above the limit
    of every configuration, at the CPU's size as on the chip at the cells'
    size (the chip readings are in PERF.md)."""
    cfg, arch, layers = _smoke()
    params = arch.make_params(cfg, seed)
    rng = np.random.default_rng(seed)
    calib = rng.standard_normal((4, *cfg["input_shape"]), dtype=np.float32)
    x = rng.standard_normal((64, *cfg["input_shape"]), dtype=np.float32)
    q8 = reference.quantize(layers, params,
                            reference.calibrate(layers, params, calib), 127)
    q4 = reference.quantize(layers, params,
                            reference.calibrate(layers, params, calib, 7), 7)
    ref = reference.int_forward(layers, q8, x)
    ctl = reference.int_forward(layers, q4, x)
    gap = reference.logit_gap_lsb(ctl, q4["out_scale"], ref, q8["out_scale"])
    assert gap > max(LIMITS.values())


def test_smoke_cells_hold_the_committed_limit():
    cfg, _, _ = _smoke()
    assert cfg["correct"]["logit_gap_lsb"] == max(LIMITS.values()) == \
        min(LIMITS.values())


def _run(root, name, on_ready=None, seed=11):
    bm = spec.Benchmark(root)
    lines = []
    res = cell.run(bm, name, seed, 1.0, False, time.perf_counter(),
                   check_device=False, on_ready=on_ready, log=lines.append)
    return res, lines


def _break(make_fault):
    """``on_ready`` hook that breaks the program's output where the jitted
    batch program produces it (``make_fault()`` gives the run its fault)."""
    def hook(server, session):
        engine = session.engine
        real = engine.run_batch_async
        fault = make_fault()

        def broken(xs, mode="float"):
            return fault(real(xs, mode))
        engine.run_batch_async = broken
    return hook


def _alter_one(out):
    """The first answer of the batch gets its top logit turned to the
    bottom of the range: another answer, produced where the program
    produces it."""
    flat = out.reshape(out.shape[0], -1)
    top = jnp.argmax(flat[0])
    return flat.at[0, top].set(-127).reshape(out.shape)


def _drop_half(out):
    half = out.shape[0] // 2
    return out.at[half:].set(0) if half else out.at[:].set(0)


def _swap_slots():
    """Each dispatch's first answer handed to the next dispatch's first
    request (a mix-up that a batch of one shows too)."""
    held = []

    def fault(out):
        first = out[0]
        if held:
            out = out.at[0].set(held[0])
        held[:] = [first]
        return out
    return fault


FAULTS = [lambda: _alter_one, lambda: _drop_half, _swap_slots]
FAULT_IDS = ["answer_altered", "half_batch_left_out", "slots_swapped"]


def _control(root, seed):
    """``on_ready`` hook that puts the control in the program's place: the
    reference at int4 computes every batch, and its logits are handed back
    as the program hands its own (int8 steps of the int8 output scale)."""
    cfg = json.loads((root / "bench" / "configs" /
                      "mnv2_smoke_int8_spatial.json").read_text())
    arch = spec.load_module(root / "bench" / "models" / f"{cfg['arch']}.py")
    layers = arch.layers(cfg)
    params = arch.make_params(cfg, seed)
    _, calib = cell._pool(cfg, seed, 1)
    q8 = reference.quantize(layers, params,
                            reference.calibrate(layers, params, calib), 127)
    q4 = reference.quantize(layers, params,
                            reference.calibrate(layers, params, calib, 7), 7)

    def hook(server, session):
        engine = session.engine
        real = engine.run_batch_async

        def control(xs, mode="float"):
            out = real(xs, mode)
            ctl = reference.int_forward(layers, q4, np.asarray(xs))
            as8 = np.clip(np.round(ctl * (q4["out_scale"] / q8["out_scale"])),
                          -127, 127)
            return jnp.asarray(as8.reshape(out.shape), out.dtype)
        engine.run_batch_async = control
    return hook


def test_sound_run_is_correct(smoke_root):  # noqa: F811
    res, lines = _run(smoke_root, "smoke-steady")
    assert res["correct"] is True
    assert res["checks"]["logit_gap_lsb"]["value"] == 0.0
    assert list(res)[-1] == "checks"
    assert lines[-1].startswith("check ") and "limit" in lines[-1]


@pytest.mark.parametrize("fault", FAULTS, ids=FAULT_IDS)
@pytest.mark.parametrize("name", ["smoke-steady", "smoke-offline"])
def test_broken_timed_path_is_not_correct(smoke_root, fault, name):  # noqa: F811
    res, _ = _run(smoke_root, name, on_ready=_break(fault))
    assert res["correct"] is False
    gap = res["checks"]["logit_gap_lsb"]
    assert gap["value"] > gap["limit"] == LIMITS["mnv2_112_int8_spatial"]


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
@pytest.mark.parametrize("name", ["smoke-steady", "smoke-offline"])
def test_control_in_the_programs_place_is_not_correct(smoke_root, name,  # noqa: F811
                                                      seed):
    res, _ = _run(smoke_root, name, on_ready=_control(smoke_root, seed),
                  seed=seed)
    assert res["correct"] is False
    gap = res["checks"]["logit_gap_lsb"]
    assert gap["value"] > gap["limit"] == LIMITS["mnv2_112_int8_spatial"]


def test_no_accelerator_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mnv2-spatial-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not proc.stdout.strip()


def test_window_without_answers_compares_nothing():
    """A window that served no answer has no gap to read: the check gives
    None (so the run is not correct) and never calls the reference on an
    empty batch."""
    assert cell.compare(None, [], [], [], None, [], 10) == \
        {"logit_gap_lsb": [None, 10]}
