"""The comparison has teeth: the control (the reference one write call
behind, in the store's place) and faults planted in the timed path
underneath the front end all come out not correct."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.control import ControlStore
from bench.store import Store
from bench.tests.tiny import CELLS, tiny_cell


def _run(cell, factory, seed=31):
    return run.run_cell(cell, jax.devices()[:1], seed, 1.0, False,
                        server_factory=factory,
                        t_start=time.perf_counter(), log=lambda m: None)


@pytest.mark.parametrize("name", CELLS)
def test_control_comes_out_not_correct(name):
    out = _run(tiny_cell(name), ControlStore)
    assert not out["correct"], out["checks"]


def _faulty(fault):
    """A Store whose backend programs break after set-up, in the window."""

    class Faulty(Store):
        def warm(self, keys, groups, sizes):
            super().warm(keys, groups, sizes)
            ops = dict(self.backend.ops)
            real = {k: ops[k] for k in ("put", "get", "scan")}
            ops.update(FAULTS[fault](real))
            self.backend.ops = ops

    return Faulty


def _state_unchanged(real):
    """put acknowledges every lane and returns the store unchanged."""
    def put(st, k, v, m):
        _, ok, addrs, nrep = real["put"](st, k, v, m)
        return st, m, addrs, nrep
    return {"put": put}


def _half_batch(real):
    """get answers the first half of its lanes; the rest come back not
    found."""
    def get(st, k, m):
        addrs, found, acc, vals, routed, vok = real["get"](st, k, m)
        half = jnp.arange(k.shape[0]) < (m.sum() + 1) // 2
        return addrs, found & half, acc, vals, routed, vok
    return {"get": get}


def _altered(real):
    """get alters one word of its first lane's value; scan alters its
    first key."""
    def get(st, k, m):
        addrs, found, acc, vals, routed, vok = real["get"](st, k, m)
        return addrs, found, acc, vals.at[0, 0].add(1), routed, vok

    def scan(st, lo, hi):
        keys, addrs, cov, st = real["scan"](st, lo, hi)
        return keys.at[0].add(1), addrs, cov, st
    return {"get": get, "scan": scan}


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "altered": _altered}


@pytest.mark.parametrize("fault,name", [
    ("state_unchanged", "paper-32b.ycsb-a"),
    ("half_batch", "ycsb-1kb.ycsb-c"),
    ("altered", "ycsb-1kb.ycsb-c"),
    ("altered", "paper-32b.ycsb-e"),
])
def test_a_fault_in_the_timed_path_is_caught(fault, name):
    out = _run(tiny_cell(name), _faulty(fault))
    assert not out["correct"], out["checks"]
