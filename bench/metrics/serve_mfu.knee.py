"""Operations of the tokens served in the window (harness.counts.
served_ops: admitted prompts' prefill and generated tokens' forward, at
their own contexts and ranks; padded slots do not count) over the window's
wall seconds times the chip's bf16 peak, in percent."""
from harness.counts import served_ops


def read(run, ctx):
    if run["job"] != "serve":
        return None
    return (100.0 * served_ops(ctx.cfg, run["steps"], run["prompt_len"])
            / (run["window_s"] * ctx.peak["bf16_flops"]))
