"""Share of its roofline that the paged-attention kernel reaches on the
decode path, in percent.

Roofline time is the larger of operations over the peak bf16 rate and bytes
over the peak HBM rate, for the attention every output token after a
request's first needed (``bench/costs/paged_attention.py``, from the
harness's own prompt lengths and token counts), delivered in the traced
window.  The kernel's time is the summed device time of its operations
inside the decode-stage programs in that window."""

from benchlib import stats
from benchlib import trace as T

DECODE = ("jit_end_step", "jit_cloud_step")
KERNEL = "paged_attention"


def read(run):
    if run.trace is None or "bf16_flops" not in run.peaks:
        return None
    lo, hi = run.trace_window
    mods = T.modules_matching(run.trace, DECODE, lo, hi)
    kern = T.ops_inside(run.trace, KERNEL, mods)
    t_kernel = sum(d for _, _, d in kern) / 1e9
    if t_kernel <= 0:
        return None
    cost = run.cost("paged_attention")
    flops = nbytes = 0.0
    for prompt_len, i in stats.decode_tokens(run.window, *run.trace_window_s):
        f, b = cost.decode_token(run.model, prompt_len + i)
        flops += f
        nbytes += b
    if flops <= 0:
        return None
    t_roof = max(flops / run.peaks["bf16_flops"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * t_roof / t_kernel
