"""What decides ``correct``, on the CPU at smoke widths, through the
whole run (the look for a chip skipped):

* the control, the reference one precision below the configuration's
  (fp8 for the bf16 the cells serve in), fails each cell's limit while
  the program passes it;
* with the timed path broken underneath, ``correct`` comes out false,
  once for each fault a cell can have: a denoise step or a decode step
  that returns its state unchanged, and an answer or a token altered
  where it is produced.
"""
import numpy as np
import pytest

from bench import run
from bench.tests import tiny

CELLS = sorted(tiny.CELLS)
SEED = 2 ** 31 + 4242


def _run(cell, **kw):
    return run.run_cell(cell, SEED, 4.0, 0, require_chip=False,
                        overrides=tiny.CELLS[cell], **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit_the_program_passes(cell):
    res = _run(cell, control=True)
    (key, check), = res["checks"].items()
    assert check["value"] <= check["limit"], res["checks"]
    assert res["control"][key] > check["limit"], (res["control"], check)
    assert res["correct"]


def _dit_unchanged(eng):
    eng._step_fn = lambda params, lat, *args: lat


def _dit_altered(eng):
    step = eng._step_fn

    def altered(params, lat, *args):
        x = step(params, lat, *args)
        return lat + 1.1 * (x - lat)        # each step moves 10% too far
    eng._step_fn = altered


def _lm_unchanged(eng):
    decode = eng._decode_fn

    def stale(params, batch, caches):
        logits, _ = decode(params, batch, caches)
        return logits, caches
    eng._decode_fn = stale


def _lm_altered(eng):
    sample = eng._sample
    calls = [0]

    def altered(logits):
        tok = sample(logits)
        calls[0] += 1
        if calls[0] % 5 == 0:
            tok = (np.asarray(tok) + 1) % logits.shape[-1]
        return tok
    eng._sample = altered


FAULTS = [("wan_dit_1_3b.denoise_backlog", _dit_unchanged),
          ("wan_dit_1_3b.denoise_backlog", _dit_altered)] + [
    ("internlm2_20b.longdoc_backlog", f)
    for f in (_lm_unchanged, _lm_altered)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    system = __import__("bench.core", fromlist=["x"]).named_module(
        "systems", run.load_cell(cell)[2]["system"])
    warm = system.System.warm

    def warm_then_break(self, traffic):
        warm(self, traffic)
        fault(self.eng)
    monkeypatch.setattr(system.System, "warm", warm_then_break)
    res = _run(cell)
    assert not res["correct"], res["checks"]
