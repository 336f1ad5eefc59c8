"""`correct` has to be able to fail.  The control (the reference in float8
put in the program's place) and each fault a cell can have come out as not
correct, at tiny sizes on the CPU, through everything of a run but the look
for a chip, against limits set at that size as the cells' are (`tiny.LIMITS`).
The readings that set the cells' own limits were taken on the chip at the
cells' own sizes (PERF.md section 2)."""

import numpy as np
import pytest

import tiny
from tiny import interpret  # noqa: F401  (a fixture)
from benchmarks import reference, run as R, traffic
from benchmarks.kinds import train_step

TRAIN_E2E = ["train_tok_s", "setup_s"]
SERVE_E2E = ["itl_p95_ms", "serve_tok_s", "setup_s"]


def train_batches(ctx):
    p = ctx.params
    toks = [np.asarray(traffic.train_tokens(ctx.seed, s, p["batch"], p["seqlen"],
                                            ctx.cfg["vocab_size"]))
            for s in range(1, p["check_steps"] + 1)]
    return [(t[:, :-1], t[:, 1:]) for t in toks]


def failed(checks):
    return sorted(k for k, c in checks.items() if not c["value"] <= c["limit"])


def test_train_half_batch_and_unchanged_state_fail_the_cells_limits():
    """The reference with half the batch left out, and a state left
    unchanged, each put in the program's place against the reference."""
    ctx = tiny.train_ctx(seed=21)
    limits = tiny.LIMITS
    batches = train_batches(ctx)
    want = reference.train_steps(ctx.cfg, ctx.seed, batches)
    assert failed(train_step.compare(want, want, limits)) == []
    half = reference.train_steps(ctx.cfg, ctx.seed, batches, fault="half_batch")
    assert failed(train_step.compare(half, want, limits)) != []
    # a state left unchanged: no moment, no change
    still = {"losses": want["losses"],
             "grad_norms": {k: 0.0 for k in want["grad_norms"]},
             "change_norms": {k: 0.0 for k in want["change_norms"]}}
    assert {"grad_gap", "change_gap"} <= set(failed(train_step.compare(still, want, limits)))


@pytest.mark.parametrize("make_ctx,e2e,number", [
    (tiny.train_ctx, TRAIN_E2E, "grad_gap"), (tiny.serve_ctx, SERVE_E2E, "logit_gap")])
def test_run_with_the_control_in_the_programs_place_is_not_correct(make_ctx, e2e, number,
                                                                   interpret):
    """What `control.py` does on the chip: a whole run, the control's
    readings compared where the program's would be."""
    sound = R.run_cell(make_ctx(seed=33), {}, e2e)
    assert sound["correct"] is True
    res = R.run_cell(make_ctx(seed=33, control=True), {}, e2e)
    assert res["correct"] is False
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]
    assert res["checks"][number]["value"] > 3 * sound["checks"][number]["value"]


def test_train_run_with_the_state_left_unchanged_is_not_correct(monkeypatch, interpret):
    import paddle_tpu as paddle

    monkeypatch.setattr(paddle.optimizer.AdamW, "step", lambda self: None)
    res = R.run_cell(tiny.train_ctx(), {}, TRAIN_E2E)
    assert res["correct"] is False
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_train_run_with_half_the_batch_left_out_is_not_correct(monkeypatch, interpret):
    from paddle_tpu.models import LlamaForCausalLM

    whole = LlamaForCausalLM.forward

    def half(self, input_ids, labels=None, attn_mask=None):
        n = input_ids.shape[0] // 2
        return whole(self, input_ids[:n], labels=labels[:n], attn_mask=attn_mask)

    monkeypatch.setattr(LlamaForCausalLM, "forward", half)
    res = R.run_cell(tiny.train_ctx(), {}, TRAIN_E2E)
    assert res["correct"] is False


def test_serve_run_with_a_token_altered_is_not_correct(monkeypatch, interpret):
    from paddle_tpu.inference.engine import ContinuousBatchingEngine as Engine

    emit = Engine._emit

    def altered(self, s, req, tok):
        # every seventh token of a request is replaced where it is produced
        return emit(self, s, req, (tok + 1) % 256 if len(req.tokens) % 7 == 3 else tok)

    monkeypatch.setattr(Engine, "_emit", altered)
    res = R.run_cell(tiny.serve_ctx(), {}, SERVE_E2E)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]
