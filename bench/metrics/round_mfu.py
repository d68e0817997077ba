"""Operations that the rounds' local training requires (harness.counts:
frozen base forward and activation gradients, LoRA at each client's own
rank, attention; no recomputation) over the window's wall seconds times
the chip's bf16 peak, in percent."""


def read(run, ctx):
    if run["job"] != "rounds" or not run["rounds"]:
        return None
    return (100.0 * sum(run["round_ops"])
            / (run["window_s"] * ctx.peak["bf16_flops"]))
