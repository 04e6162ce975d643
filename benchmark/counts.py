"""The roofline arithmetic that is the same for every model. Operations
and bytes the MODEL needs come from its shapes alone, in its family's file
(`families/<family>.py`: `matmul_params`, `forward_flops`,
`train_flops_per_token`, `attention_train_flops/_bytes`,
`kv_bytes_per_token`, `weight_bytes`, `decode_step_bytes`), which the
readers reach through `cell.family`. They never depend on what implements
a step, so a kernel that replaces an XLA fusion leaves every share defined.
"""
from __future__ import annotations


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bytes: float):
    """(share in %, which bound applies) of the least time the chip could
    take over the time it took."""
    t_f, t_b = flops / peak_flops, nbytes / peak_bytes
    bound = "compute" if t_f >= t_b else "memory"
    return 100.0 * max(t_f, t_b) / seconds, bound
