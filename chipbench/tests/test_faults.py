"""A run drives the rest of the harness over a timed path broken
underneath (the chip check skipped, a tiny model on the CPU) and sees
``correct`` come out false for each fault a serving cell can have."""
import jax.numpy as jnp
import pytest

import tiny
from chipbench import harness as H


def state_unchanged(step, slots, vocab):
    def f(params, caches, state):
        _, _, rec = step(params, caches, state)
        return state, caches, rec
    return f


def half_batch(step, slots, vocab):
    keep = jnp.arange(slots) < slots // 2

    def f(params, caches, state):
        new, caches, rec = step(params, caches, state)

        def pick(a, b):
            if a is None or a.ndim == 0 or a.shape[0] != slots:
                return a
            k = keep.reshape((slots,) + (1,) * (a.ndim - 1))
            return jnp.where(k, a, b)
        import jax
        return jax.tree.map(pick, new, state), caches, rec
    return f


def token_altered(step, slots, vocab):
    def f(params, caches, state):
        state, caches, rec = step(params, caches, state)
        tok = jnp.where(rec["emit"], (rec["token"] + 1) % vocab, rec["token"])
        return state, caches, dict(rec, token=tok)
    return f


def run(monkeypatch, fault):
    from repro.models import registry as REG
    # a backlog keeps every slot busy, so a fault in any slot shows
    cfg, mix = tiny.fresh(tiny.CONFIG), tiny.fresh(tiny.DECODE)
    mix["drain_seconds"] = 5
    if fault is not None:
        orig = REG.build_serve_step

        def broken(*a, **k):
            return fault(orig(*a, **k), cfg["serve"]["slots"],
                         cfg["vocab_size"])
        monkeypatch.setattr(REG, "build_serve_step", broken)
    return H.run_cell(tiny.cell(mix), cfg, mix, [], 2**31 + 17, 1.5, False,
                      require_tpu=False)


def test_sound_run_is_correct(cache_dir, monkeypatch):
    res = run(monkeypatch, None)
    assert res["correct"], res["checks"]
    assert res["checks"]["served_gap"]["value"] <= 0.5
    # every slot was busy when the window opened
    assert res["attempted"] >= tiny.CONFIG["serve"]["slots"]
    assert res["failed"] == 0


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
def test_fault_is_not_correct(cache_dir, monkeypatch, fault):
    res = run(monkeypatch, fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("mode,correct", [("sound", True),
                                          ("no_exchange", False)])
def test_exchange_between_chips(cache_dir, mode, correct):
    """Four virtual devices: the merge (psum) of the chips' partial
    decode attention left out."""
    import json
    import os
    import pathlib
    import subprocess
    import sys
    script = pathlib.Path(__file__).with_name("four_devices.py")
    p = subprocess.run([sys.executable, str(script), mode],
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ))
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4
    assert res["correct"] is correct, res
    if mode == "no_exchange":
        assert res["psum_calls"] > 0
