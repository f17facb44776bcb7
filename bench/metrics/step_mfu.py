"""The whole served step's share of the chip's bf16 peak, in percent: model
operations (``bench/costs/model_flops.py``) of every prompt whose first
token came back inside the window and of every later output token
delivered in it (a closed loop's pre-roll requests included), over the window's seconds times the peak."""

from benchlib import stats


def read(run):
    if "bf16_flops" not in run.peaks:
        return None
    w = run.window
    cost = run.cost("model_flops")
    flops = 0.0
    for r in stats.served(w):
        if r.token_times and w.t0 <= r.token_times[0] < w.t1:
            flops += cost.prompt_flops(run.model, len(r.req.prompt))
    for prompt_len, i in stats.decode_tokens(w, w.t0, w.t1):
        flops += cost.token_flops(run.model, prompt_len + i)
    return 100.0 * flops / ((w.t1 - w.t0) * run.peaks["bf16_flops"])
