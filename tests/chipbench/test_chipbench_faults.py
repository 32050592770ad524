"""A whole run of the tiny cell, past the harness's look for a chip, with
the served path broken underneath: ``correct`` must come out false."""

from __future__ import annotations

import time

import numpy as np
import pytest

import chipbench_tiny
from chipbench import harness
from chipbench.run import execute
from repro.serving.step import DeviceStepper


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("chipbench_cache"))


def _run(monkeypatch, cache_dir):
    monkeypatch.setattr(harness, "CACHE", cache_dir)
    return execute(chipbench_tiny.cell("gelu"), seed=2 ** 31 + 21,
                   seconds=3.0, trace=False, t_start=time.time())


def test_sound_run_is_correct(monkeypatch, cache_dir):
    res = _run(monkeypatch, cache_dir)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] == 6
    assert set(res["metrics"]) == {"ttft_p50_ms", "itl_p50_ms",
                                   "hbm_peak_gib", "setup_s"}
    assert list(res)[-1] == "checks"
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]


def test_token_altered_where_produced(monkeypatch, cache_dir):
    real = DeviceStepper.decode

    def altered(self, *a, **k):
        tok, ok = real(self, *a, **k)
        return (tok + 1) % self.cfg.vocab, ok

    monkeypatch.setattr(DeviceStepper, "decode", altered)
    res = _run(monkeypatch, cache_dir)
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_decode_step_leaves_its_cache_unchanged(monkeypatch, cache_dir):
    real = DeviceStepper.decode

    def stale(self, *a, **k):
        cache = self.cache
        out = real(self, *a, **k)
        self.cache = cache
        return out

    monkeypatch.setattr(DeviceStepper, "decode", stale)
    res = _run(monkeypatch, cache_dir)
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_a_request_that_never_answers(monkeypatch, cache_dir):
    """Every request admitted after the warm-up is dropped unanswered."""
    real = DeviceStepper.prefill
    calls = []

    def dropping(self, tokens, targets, lens):
        calls.append(1)
        logits = real(self, tokens, targets, lens)
        return logits * np.nan if len(calls) > 8 else logits

    monkeypatch.setattr(DeviceStepper, "prefill", dropping)
    monkeypatch.setattr(harness, "TAIL_S", 3.0)
    res = _run(monkeypatch, cache_dir)
    assert res["correct"] is False
    assert res["checks"]["unanswered"]["value"] > 0
