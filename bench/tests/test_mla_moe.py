"""The DeepSeek-V2-Lite decode-instance cell on the CPU at smoke widths:
the harness runs it correct against bench/reference/mla_moe.py and the
fp8 control fails its limits, the compared requests span the slots,
planted faults (an altered token, a decode step that keeps its pool)
turn ``correct`` false, ServeEngine's logits match the reference's, and
the cell's readers read what the run recorded."""
import numpy as np
import pytest

import jax

from bench import run
from bench.core import named_module

CELL = "deepseek_v2_lite.decode_instance"
# Smoke widths with the published layout: a dense first layer, MLA (q-LoRA
# off) and a share of an expert layer that is not its first experts.
SMOKE = {"num_hidden_layers": 3, "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "intermediate_size": 128,
         "moe_intermediate_size": 32, "router_num_experts": 16,
         "n_routed_experts": 4, "expert_offset": 4,
         "num_experts_per_tok": 3, "vocab_size": 512,
         "sla2": {"block_q": 32, "block_k": 16, "k_frac": 0.25},
         "max_position_embeddings": 512}
# Limits set like the cell's own, from readings at this size (CPU, seeds
# 1-3, 12345 and 2**31 + 5, 77, 99, 4242; three slots compared):
# logit_gap program 0-0.177 against the fp8 control's 0.25-0.57,
# mean_gap 0-0.00136 against 0.0121-0.0313, off_argmax_share 0-0.026
# against 0.118-0.245.
OVERRIDES = {
    "config": SMOKE,
    "traffic": {"clients": 3, "engine": {"max_slots": 3, "max_len": 320},
                "sizes": {"prompt": {"dist": "uniform", "lo": 64, "hi": 160},
                          "output": {"dist": "uniform", "lo": 24,
                                     "hi": 64}},
                "check": {"requests": 4}, "trace_seconds": 1},
    "limits": {"limits": {"logit_gap": 0.2, "mean_gap": 0.004,
                          "off_argmax_share": 0.055}}}


def _run(seed=2 ** 31 + 99, seconds=2.0, **kw):
    return run.run_cell(CELL, seed, seconds, 0, require_chip=False,
                        overrides=OVERRIDES, **kw)


def test_decode_instance_runs_correct_on_cpu():
    records = []
    res = _run(records=records)
    assert res["correct"], res["checks"]
    assert res["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s"}
    rec = records[0]
    # the set-up filled every slot: the window's first step decodes three
    # rows and prefills nothing, and those requests are counted
    first = rec.steps[0]
    assert first["occupied"] == 3 and len(first["decode_rows"]) == 3
    assert first["prefill"] is None
    assert len(rec.requests) == res["attempted"] >= 3
    assert all(t >= 0 for r in rec.requests for t in r["tokens"])
    assert rec.counters["moe_held_rows"] > 0


def test_control_fails_the_limits_the_program_passes():
    """The fp8 control fails a limit the program passes."""
    res = _run(control=True)
    assert res["correct"], res["checks"]
    assert any(res["control"][key] > check["limit"]
               for key, check in res["checks"].items()), (res["control"],
                                                          res["checks"])


def test_sample_spans_slots():
    """The compared requests come from distinct slots: the longest
    request and seeded others, one per slot that served."""
    mla_moe = named_module("systems", "mla_moe")
    seen = []
    compare = mla_moe.System.compare

    def spy(self, precision="fp32"):
        seen.append([self.info[u]["slot"] for u in self.sample()])
        return compare(self, precision)
    mp = pytest.MonkeyPatch()
    mp.setattr(mla_moe.System, "compare", spy)
    try:
        res = _run()
    finally:
        mp.undo()
    slots, = seen
    n_slots = OVERRIDES["traffic"]["engine"]["max_slots"]
    assert len(slots) == len(set(slots)) == n_slots
    assert res["_numbers"]["compared_requests"] == n_slots


def _altered(eng):
    """Every fifth sampled token is altered where it is produced."""
    sample, calls = eng._sample, [0]

    def altered(logits):
        tok = sample(logits)
        calls[0] += 1
        if calls[0] % 5 == 0:
            tok = (np.asarray(tok) + 1) % logits.shape[-1]
        return tok
    eng._sample = altered


def _unchanged(eng):
    """The decode step returns the pool it was given: no latent, page
    table or SLA2 total of the window's tokens is kept."""
    decode = eng._decode_fn

    def stale(params, batch, caches):
        logits, _ = decode(params, batch, caches)
        return logits, caches
    eng._decode_fn = stale


@pytest.mark.parametrize("fault", [_altered, _unchanged],
                         ids=lambda f: f.__name__.strip("_"))
def test_planted_fault_turns_correct_false(fault, monkeypatch):
    mla_moe = named_module("systems", "mla_moe")
    warm = mla_moe.System.warm

    def faulty_warm(self, traffic):
        warm(self, traffic)
        fault(self.eng)
    monkeypatch.setattr(mla_moe.System, "warm", faulty_warm)
    res = _run()
    assert not res["correct"], res["checks"]


def test_engine_logits_match_the_reference():
    """Chunked prefill then paged decode through ServeEngine, in float32
    weights and pool, against the reference's logits for every served
    token.  Tolerance 2e-3: float32 rounding between the program's
    absorbed latent attention and the reference's per-head decompressed
    keys and values, through three layers; a differently routed block or
    expert moves logits by ~1e-1."""
    from bench.reference import mla_moe as ref
    from bench.systems.mla_moe import model_config
    from repro.models.api import build_model
    from repro.serve import EngineConfig, Request, ServeEngine
    spec = run.load_cell(CELL, OVERRIDES)[2]
    spec["dtype"] = "float32"
    model = build_model(model_config(spec))
    params = model.init(jax.random.PRNGKey(1))
    eng = ServeEngine(model, EngineConfig(max_slots=2, max_len=320,
                                          prefill_chunk=64,
                                          page_dtype="float32"))
    eng.load(params)
    seen = {}

    def pull(logits, fn=eng._logits_to_host):
        out = fn(logits)
        if out.shape[0] == 1:        # a prompt's last chunk
            slots = [s for s, st in eng._slots.items()
                     if not st.decoding and st.pos == len(st.tokens)]
        else:
            slots = [s for s, st in eng._slots.items() if st.decoding]
        for s in slots:
            seen.setdefault(eng._slots[s].req.uid, []).append(
                out[0 if out.shape[0] == 1 else s])
        return out
    eng._logits_to_host = pull
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, SMOKE["vocab_size"], n).astype(np.int32)
               for n in (150, 70)]
    for uid, p in enumerate(prompts):
        eng.submit(Request(uid=uid, prompt=p, max_new_tokens=40))
    done = {r.uid: r for r in eng.run_to_completion()}
    for uid, p in enumerate(prompts):
        out = np.asarray(done[uid].output)
        seq = np.concatenate([p, out[:-1]]).astype(np.int32)
        want = ref.logits(params, spec, seq, len(p), eng.max_len, "fp32")
        got = np.stack(seen[uid])
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-3)


def test_readers_read_the_counters():
    records = []
    _run(records=records)
    rec = records[0]
    rec.extra["part_steps"] = rec.steps
    rows = named_module("metrics",
                        "moe_rows_per_expert.dsv2_decode").read(rec)
    n_moe = SMOKE["num_hidden_layers"] - 1
    want = rec.counters["moe_held_rows"] / (
        len(rec.steps) * n_moe * SMOKE["n_routed_experts"])
    assert rows == pytest.approx(want) and rows > 0
    peaks = {"flops_bf16": 197e12, "hbm_bw": 819e9}
    rec.peaks = peaks
    rec.trace = {"kernels": {"moe_experts": {"time_s": 1.0, "count": 4}}}
    share = named_module("metrics", "moe_experts_roofline").read(rec)
    from bench.costs import moe_experts as C
    ideal = sum(C.ideal_s(*C.per_calls(s["moe_held_rows"],
                                       s["moe_active_experts"], d=64, ff=32),
                          peaks) for s in rec.steps)
    assert share == pytest.approx(100.0 * ideal)
    flops = rec.extra["flops_fn"](rec.steps)
    assert flops > 0
    # without the engine's counters (a program that lacks them) the
    # readers report nothing
    rec.counters.pop("moe_held_rows")
    for s in rec.steps:
        s.pop("moe_held_rows")
    assert named_module("metrics", "moe_rows_per_expert").read(rec) is None
    assert named_module("metrics", "moe_experts_roofline").read(rec) is None


def test_witness_lies_between_reference_and_control():
    """The reference in bf16 (the witness) departs from the float32
    reference, and by much less than the fp8 control does: e4m3's
    rounding step is 16x bfloat16's, so the typical (median) logit error
    is at least 4x smaller in the witness."""
    from bench.reference import mla_moe as ref
    from bench.systems.mla_moe import model_config
    from repro.models.api import build_model
    spec = run.load_cell(CELL, OVERRIDES)[2]
    params = build_model(model_config(spec)).init(jax.random.PRNGKey(2))
    seq = np.random.default_rng(3).integers(
        1, SMOKE["vocab_size"], 150).astype(np.int32)
    out = {p: ref.logits(params, spec, seq, 100, 320, p)
           for p in ("fp32", "bf16", "fp8")}
    err = {p: np.median(np.abs(out[p] - out["fp32"]))
           for p in ("bf16", "fp8")}
    assert 0 < err["bf16"] < err["fp8"] / 4, err
