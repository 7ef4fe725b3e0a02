"""Train step against the bf16 peak: the least time the chip could take
for the operations a step requires (`costs.gpt_train_flops_per_token`)
over the device's busy time per step in the traced window."""


def read(ctx):
    t = ctx.get("trace")
    if not t or not ctx.get("traced_steps"):
        return None
    least = ctx["flops_per_step"] / ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * least / (t["busy_s"] / ctx["traced_steps"])
