"""A run whose timed path is broken underneath must come out not correct.

Each fault is planted in the program's device sweep, which both drivers'
windows run; the harness is driven as in a real run, with the CPU in the
chip's place. The fault of an exchange between chips does not apply: no
cell spans chips.
"""

from __future__ import annotations

import dataclasses

import pytest

from repro.sim import jax_engine


def _break_commit_step(monkeypatch, fault):
    build = jax_engine._build_commit_step

    def broken_build(mode):
        step = build(mode)

        def broken(tier, *args):
            out, interf, vsel = step(tier, *args)
            return fault(tier, out), interf, vsel

        return broken

    monkeypatch.setattr(jax_engine, "_build_commit_step", broken_build)


def unchanged(monkeypatch):
    """The commit step returns its state unchanged."""
    _break_commit_step(monkeypatch, lambda tier_in, tier_out: tier_in)


def half_left_out(monkeypatch):
    """The commit step leaves half of the batch (the first half of the
    size rows) out."""

    def fault(tier_in, tier_out):
        h = tier_in.shape[0] // 2
        return tier_out.at[:h].set(tier_in[:h])

    _break_commit_step(monkeypatch, fault)


def answer_altered(monkeypatch):
    """An answer altered where it is produced: every interval that
    reaches the slow tier costs a nanosecond more. The program's own
    checks cannot see it."""
    cost = jax_engine.interval_time

    def altered(hw, **kw):
        c = cost(hw, **kw)
        return dataclasses.replace(c, t_stall=c.t_stall + 1e-9) if kw["pacc_s"] else c

    monkeypatch.setattr(jax_engine, "interval_time", altered)


@pytest.mark.parametrize("fault", [unchanged, half_left_out, answer_altered])
@pytest.mark.parametrize("cell", ["gups8g.sweep46", "perfdb.build"])
def test_broken_timed_path_is_not_correct(run_cell, monkeypatch, cell, fault):
    fault(monkeypatch)
    rc, res, err = run_cell(cell, seconds=1)
    assert rc == 0, err
    assert res["correct"] is False
    assert res["failed"] > 0
    assert any(c["value"] > c["limit"] for c in res["compared"].values())
    if fault is answer_altered:
        assert "program_errors" not in res["compared"]
