"""The matmul kernels' share of their roofline, in %: the least time the
rounds' matmul work could take on this chip, max(model FLOPs / peak FLOP/s,
least bytes / HBM bandwidth) from ``bench/flops``, over the summed device
time of the operations that contain a dot or a convolution.  With 2,048
tokens a client step the FLOPs bound is the larger.  Recomputation that
the program does is not in the FLOPs, so it lowers the share."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["rounds"] or tr["dot_s"] <= 0:
        return None
    work, peaks = ctx["work"], ctx["peaks"]
    least = max(work["flops"] / peaks["bf16_flops_per_s"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * tr["rounds"] / tr["dot_s"]
