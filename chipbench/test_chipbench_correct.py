"""What decides ``correct``, at a size a CPU test can hold.

The program's place is taken by the plain reference computed at the
program's stream width (bfloat16 block inputs and weights), so the whole
of a run after the look for a chip is driven: weights and inputs from the
seed, warm-up, the window, the sample, the comparison.  A sound stand-in
must come out correct; the float8 control, half of a batch left out, and
one answer altered where it is produced must not.
"""
import io
import json
import os
import sys
import time
from contextlib import redirect_stdout

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import body  # noqa: E402
import run  # noqa: E402
from calibrate import FAULTS  # noqa: E402

CELLS = ("mnv2-b128-bf16", "mnv1-b128-bf16", "mnv2-b1-bf16", "mnv1-b1-bf16")
SEED = 2**31 + 4321


def _round_bf16(a):
    import jax.numpy as jnp
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _small(name):
    """The cell at 32x32 inputs and at most 4 images a call (at 16x16
    the V1 body shrinks to one pixel by block 11 and the float8 control
    reads only 0.06)."""
    cell = run.load_cell(name)
    cell["config"] = dict(cell["config"], body_input=[32, 32, 32])
    tr = cell["traffic"]
    cell["traffic"] = dict(tr, batch=min(tr["batch"], 4), pool=2, sample=2,
                           warmup_calls=1)
    return cell


def stand_in(precision, fault=None):
    """A Program look-alike: the reference in the program's place."""
    import jax

    class StandIn:
        def __init__(self, cfg, bd, params, interpret=False):
            rnd = body.round_fp8 if precision == "fp8" else _round_bf16
            p32 = jax.tree_util.tree_map(lambda a: a.astype("float32"),
                                         params)

            def fn(x):
                y = bd.forward(p32, x, rnd=rnd).astype(bd.stream)
                return fault(y) if fault else y

            self.fn = jax.jit(fn)

        def __call__(self, x):
            return self.fn(x)

        def free(self):
            self.fn = None

    return StandIn


@pytest.fixture
def drive(monkeypatch, tmp_path):
    """Runs a small cell; the run's stores and environment stay in the
    test."""
    monkeypatch.setattr(run, "STATE_DIR", str(tmp_path))
    for var in ("REPRO_QUARANTINE", "REPRO_TUNE_CACHE", "TPU_LOG_DIR"):
        monkeypatch.setenv(var, "")
    return _drive


def _drive(name, make_program):
    cell = _small(name)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.run(cell, SEED, 0.2, False, device_check=False,
                     make_program=make_program, t_start=time.perf_counter())
    assert rc == 0
    result = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    return result


@pytest.mark.parametrize("name", CELLS)
def test_sound_stand_in_is_correct(drive, name):
    r = drive(name, stand_in("bf16"))
    assert r["correct"] is True and r["failed"] == 0
    chk = r["checks"]["max_rel_err"]
    assert 0 < chk["value"] < chk["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_fp8_control_is_not_correct(drive, name):
    r = drive(name, stand_in("fp8"))
    assert r["correct"] is False
    assert r["checks"]["max_rel_err"]["value"] > 3 * 0.012


@pytest.mark.parametrize("name", CELLS)
def test_answer_altered_is_not_correct(drive, name):
    r = drive(name, stand_in("bf16", FAULTS["answer_altered"]))
    assert r["correct"] is False and r["failed"] > 0


@pytest.mark.parametrize("name", ("mnv2-b128-bf16", "mnv1-b128-bf16"))
def test_half_batch_left_out_is_not_correct(drive, name):
    r = drive(name, stand_in("bf16", FAULTS["half_batch_left_out"]))
    assert r["correct"] is False
    assert r["checks"]["max_rel_err"]["value"] == pytest.approx(1.0)
