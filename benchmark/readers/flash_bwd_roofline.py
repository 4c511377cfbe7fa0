"""The flash-attention BACKWARD kernel's share of its roofline, whatever the
rank of its operands: the least time the chip could take for the traced
calls (per call the larger of flops over peak FLOP/s and bytes over peak
bytes/s, ``trace/opsbytes.py flash_backward``) over the time the calls
took on the device.

``flash_roofline.py`` knows the call by three 4-d operands first. A
backward kernel that reads q, k, v and lse of one layer where a loop over
layers stacked them takes a prefetched ``s32[1]`` first and the
``[layers, b, rows, s, D]`` stacks whole, and is out of that reader's
sight though it is the same kernel doing the same work. Here the call is
known by its three results, ``[b, rows, s, D]`` each, and by the LAST four
dims of its first three operands of four dims or more, which are the
results' dims: one layer's q, k, v, stacked or not.

The work is counted in real heads: a row holds ``D / head_dim`` of them
side by side (head dim 64 in 128 lanes: 2), and a configuration whose head
count does not fill the last row (25 heads in 13 rows) has a zero head
there that is no work the algorithm needs. Head count and width are the
configuration's (``n_head``, ``n_embd``)."""
from benchmark.trace import opsbytes


def backward_call(kernel: dict):
    """A traced Pallas call -> (b, rows, sq, sk, D) where it is the flash
    backward, (q, k, v, dO, lse, delta) -> (dq, dk, dv) behind any
    prefetched scalar, else None."""
    outs = [tuple(dims) for _, dims in kernel["outputs"]]
    ops = [tuple(dims) for _, dims in kernel["operands"] if len(dims) >= 4]
    if len(outs) != 3 or len(ops) != 6 or any(len(o) != 4 for o in outs):
        return None
    if [o[-4:] for o in ops[:3]] != outs or outs[1] != outs[2]:
        return None
    (b, rows, sq, d), sk = outs[0], outs[1][2]
    return b, rows, sq, sk, d


def read(ctx, causal: bool = True):
    trace, peaks, cfg = ctx.get("trace"), ctx["peaks"], ctx.get("config", {})
    if not trace or not cfg.get("n_head") or not cfg.get("n_embd"):
        return None
    head_dim = cfg["n_embd"] // cfg["n_head"]
    least = measured = 0.0
    for k in trace.get("kernels", []):
        found = backward_call(k)
        if found is None or found[-1] % head_dim:
            continue
        b, rows, sq, sk, d = found
        heads = min(rows * (d // head_dim), cfg["n_head"])
        flops, nbytes = opsbytes.flash_backward(
            b, heads, sq, sk, head_dim, causal,
            opsbytes.DTYPE_BYTES[k["outputs"][0][0]])
        least += k["calls"] * max(flops / peaks["bf16_flops_per_s"],
                                  nbytes / peaks["hbm_bytes_per_s"])
        measured += k["seconds"]
    return 100.0 * least / measured if measured else None
