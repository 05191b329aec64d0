"""Per-layer metrics read from the program's own counters: present where
the engine keeps the counter, None (never an error) where it does not."""
from bench import run
from bench.core import Record, named_module
from bench.tests import tiny

LONGDOC = "internlm2_20b.longdoc_backlog"


def _rec(counters, steps):
    return Record(cell={}, config={}, traffic={}, seconds=1.0,
                  counters=counters, steps=steps)


def test_logits_to_host_bytes_per_step():
    read = named_module("metrics", "logits_to_host_bytes.longdoc").read
    steps = [{}, {}, {}, {"drain": True}]
    assert read(_rec({"logits_to_host_bytes": 3000}, steps)) == 1000
    # an engine without the counter, or a window without steps
    assert read(_rec({"prefill_tokens": 64}, steps)) is None
    assert read(_rec({"logits_to_host_bytes": 0}, [])) is None


def test_logits_to_host_bytes_in_a_traced_cpu_run():
    """The traced run reports the metric: the first token of each prompt
    pulls (1, V) float32 logits, each decode step (slots, V)."""
    res = run.run_cell(LONGDOC, 2 ** 31 + 7, 2.0, 1, require_chip=False,
                       overrides=tiny.CELLS[LONGDOC])
    v = res["metrics"]["logits_to_host_bytes.longdoc"]
    assert v["unit"] == "bytes"
    vocab = tiny.LM["config"]["vocab_size"]
    slots = tiny.LONGDOC["traffic"]["engine"]["max_slots"]
    assert 0 < v["value"] <= 4 * vocab * (slots + 1)
