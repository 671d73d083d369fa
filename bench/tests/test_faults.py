"""A whole run (the CPU rehearsal, which skips the look for a chip) with
the timed path broken underneath: ``correct`` comes out false for every
fault a one-chip serving cell can have, and true with nothing broken."""
import json

import numpy as np
import pytest

CELL = "qwen3-8b.chat-backlog"


def _run(capsys):
    from bench import run

    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 3),
                   "--seconds", "2", "--trace", "0", "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _altered_token(mp):
    """A token altered where it is produced: the sampler's argmax moves."""
    from repro.serve.scheduler import PagedScheduler

    real = PagedScheduler._sample

    def sample(self, i, lane, row, now):
        row = np.array(row)
        row[(int(np.argmax(row)) + 1) % row.shape[-1]] = row.max() + 1.0
        return real(self, i, lane, row, now)

    mp.setattr(PagedScheduler, "_sample", sample)


def _state_unchanged(mp):
    """A step that returns its state unchanged: no KV page is written."""
    import repro.serve.scheduler as sched

    real = sched.make_paged_step

    def make(cfg):
        step = real(cfg)

        def frozen(params, caches, *a):
            logits, _ = step(params, caches, *a)
            return logits, caches

        return frozen

    mp.setattr(sched, "make_paged_step", make)


def _half_batch(mp):
    """Half of the batch left out: the second half of every call's rows
    gets the first half's results."""
    import repro.serve.scheduler as sched

    real = sched.make_paged_step

    def make(cfg):
        step = real(cfg)

        def half(params, caches, tokens, *a):
            logits, caches = step(params, caches, tokens, *a)
            n = logits.shape[0]
            if n > 1:
                keep = (n + 1) // 2
                logits = logits.at[keep:].set(logits[: n - keep])
            return logits, caches

        return half

    mp.setattr(sched, "make_paged_step", make)


def test_sound_run_is_correct(capsys):
    out = _run(capsys)
    assert out["correct"] is True
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged,
                                   _half_batch])
def test_fault_is_caught(fault, capsys, monkeypatch):
    fault(monkeypatch)
    out = _run(capsys)
    assert out["correct"] is False
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
