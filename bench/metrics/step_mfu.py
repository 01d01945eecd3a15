"""The whole step's share of the chip's bf16 peak over the traced window:
model FLOPs of the rounds traced (``bench/flops``) / window / peak, in %.
It bounds ``kernels.matmul_roofline`` from the outside: a change that takes
a kernel off the path leaves that metric silent, not this one."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0 or not tr["rounds"]:
        return None
    return (100.0 * ctx["work"]["flops"] * tr["rounds"] / tr["window_s"]
            / ctx["peaks"]["bf16_flops_per_s"])
