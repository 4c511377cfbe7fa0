"""The flash-attention FORWARD kernel's share of its roofline, whatever the
layout of its operands and results: the least time the chip could take
for the traced calls (per call the larger of flops over peak FLOP/s and
bytes over peak bytes/s, ``trace/opsbytes.py flash_forward``) over the time
the calls took on the device.

``flash_roofline.py`` knows the forward by three 4-d operands first and a
second result ``lse[b, h, sq, 1]`` with the operands' ``h``. A forward on
packed rows (``[b, rows, s, D]``, ``D / head_dim`` heads side by side)
writes lse a head, ``[b, rows x D / head_dim, sq, 1]``; one that writes its
layer of the ``[layers, ...]`` stacks a loop over layers saves takes a
prefetched ``s32[1]`` first, the two stacks as operands aliased to its
results, and lse as rows of lanes, ``[layers, b, heads, sq // n, n]``.
Each is out of that reader's sight though it is the same kernel doing the
same work. Here the call is known by its TWO results and by the LAST four
dims of its first three operands of four dims or more: one layer's q, k,
v. o is q's dims, behind a layers dim or not; lse is ``[b, heads, sq, 1]``
or ``[b, heads, sq // n, n]`` behind the same.

The work is counted in real heads, as ``flash_bwd_roofline.py`` counts it:
a configuration whose head count does not fill the last row (25 heads in
13 rows) has a zero head there that is no work the algorithm needs. Head
count and width are the configuration's (``n_head``, ``n_embd``)."""
from benchmark.trace import opsbytes


def forward_call(kernel: dict):
    """A traced Pallas call -> (b, rows, sq, sk, D) where it is the flash
    forward, (q, k, v) -> (o, lse) behind any prefetched scalar and before
    any stack it writes in place, else None."""
    outs = [tuple(dims) for _, dims in kernel["outputs"]]
    ops = [tuple(dims) for _, dims in kernel["operands"] if len(dims) >= 4]
    if len(outs) != 2 or len(ops) < 3 or len(outs[0]) < 4:
        return None
    q, k, v = (o[-4:] for o in ops[:3])
    o, lse = outs[0][-4:], outs[1][len(outs[0]) - 4:]
    (b, rows, sq, d), sk = q, k[2]
    if o != q or k != v or k != (b, rows, sk, d) or len(lse) != 4:
        return None
    if lse[0] != b or lse[1] % rows or lse[2] * lse[3] != sq:
        return None
    return b, rows, sq, sk, d


def read(ctx, causal: bool = True):
    trace, peaks, cfg = ctx.get("trace"), ctx["peaks"], ctx.get("config", {})
    if not trace or not cfg.get("n_head") or not cfg.get("n_embd"):
        return None
    head_dim = cfg["n_embd"] // cfg["n_head"]
    least = measured = 0.0
    for k in trace.get("kernels", []):
        found = forward_call(k)
        if found is None or found[-1] % head_dim:
            continue
        b, rows, sq, sk, d = found
        heads = min(rows * (d // head_dim), cfg["n_head"])
        flops, nbytes = opsbytes.flash_forward(
            b, heads, sq, sk, head_dim, causal,
            opsbytes.DTYPE_BYTES[k["outputs"][0][0]])
        least += k["calls"] * max(flops / peaks["bf16_flops_per_s"],
                                  nbytes / peaks["hbm_bytes_per_s"])
        measured += k["seconds"]
    return 100.0 * least / measured if measured else None
