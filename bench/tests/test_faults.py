"""A whole run on the CPU, without the look for a chip, with the timed path
broken underneath: ``correct`` must come out false for each fault a serving
cell can have.  (The exchange between chips is not among them: no cell runs
on more than one chip.)"""

import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import run_cell
from repro.models import attention
from repro.serve import serving


def run(tmp_path, mix="tiny-offline"):
    root = tiny.make_root(tmp_path, [("stablelm-12b", "dequant", mix)])
    cell = run_cell.Cell.load(root, f"stablelm-12b-dequant-{mix}")
    return run_cell.run(cell, 2**31 + 3, 1.5, trace=False, device_check=False)


def token_altered(monkeypatch):
    """Every token the engine fetches from the device, shifted by one id."""
    fetch = serving.ServeEngine._fetch
    monkeypatch.setattr(serving.ServeEngine, "_fetch",
                        lambda self, x: np.where(fetch(self, x) >= 0,
                                                 (fetch(self, x) + 1) % 512, -1))


def state_unchanged(monkeypatch):
    """The KV cache write returns the cache as it was."""
    monkeypatch.setattr(attention, "_cache_write", lambda cache, new, pos: cache)


def half_the_batch(monkeypatch):
    """The decode wave runs only the first half of the slots."""
    make = serving.make_decode_wave

    def halved(model, *, ctx=None, out_cap):
        wave = make(model, ctx=ctx, out_cap=out_cap)

        def run_half(params, token, caches, pos, pad, active, steps):
            keep = jnp.arange(active.shape[0]) < active.shape[0] // 2
            return wave(params, token, caches, pos, pad, active & keep, steps)

        return run_half

    monkeypatch.setattr(serving, "make_decode_wave", halved)


@pytest.mark.parametrize("fault", [token_altered, state_unchanged, half_the_batch])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    res = run(tmp_path)
    assert not res["correct"], res["checks"]


def test_the_unbroken_path_is_correct(tmp_path):
    assert run(tmp_path)["correct"]
