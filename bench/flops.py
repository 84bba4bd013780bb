"""Byte counts the per-layer metrics divide by, and the comm buckets, from
shapes. Model FLOPs and parameter sizes belong to each configuration's
family (`bench/families/<family>.py`).

Bytes of the fused EF + int8 quantize kernel (`kernels/quantize.py`), per
call over an (R, C) tile of a bucket: it reads the message, the residual
and the uniform draws (f32 each), and writes the int8 codes, one f32 scale
per row and the new residual (f32). Every operand is loaded from VMEM and
every result stored there; those the compiler placed in HBM also cross
HBM. The least time of a call is the longest of the three streams at its
peak.
"""
from __future__ import annotations


def quantize_kernel_arrays(rows: int, cols: int):
    """Bytes of each operand (message, residual, uniforms) and each result
    (codes, scales, new residual) of one fused EF + int8 quantize call over
    (rows, cols), in the kernel's order."""
    n = rows * cols
    return [4 * n, 4 * n, 4 * n], [n, 4 * rows, 4 * n]


def bucket_layout(sizes, workers: int, bucket_mb: float = 4.0):
    """The flat comm buckets the uniform plan packs leaves of `sizes` into:
    greedy in order, a bucket closed before it would pass `bucket_mb` MiB
    of f32, each padded to a multiple of workers * 1024. Returns
    [(padded size, [leaf index, ...])]."""
    cap = max(1, int(bucket_mb * (1 << 20)) // 4)
    align = workers * 1024
    out, members, used = [], [], 0
    for i, n in enumerate(sizes):
        if used and used + n > cap:
            out.append((-(-used // align) * align, members))
            members, used = [], 0
        members.append(i)
        used += n
    if used:
        out.append((-(-used // align) * align, members))
    return out


def least_seconds(rows: int, cols: int, spaces_in, spaces_out,
                  peaks: dict):
    """(seconds, bound) of one quantize call's roofline: the longest of
    its VMEM loads, its VMEM stores, and the bytes of its operands and
    results in HBM (memory space 0), each at the device's peak."""
    ins, outs = quantize_kernel_arrays(rows, cols)
    hbm = sum(b for b, sp in zip(ins + outs, list(spaces_in)
                                 + list(spaces_out)) if sp == 0)
    times = {"hbm": hbm / (peaks["hbm_gbps"] * 1e9),
             "vmem_load": sum(ins) / (peaks["vmem_read_gbps"] * 1e9),
             "vmem_store": sum(outs) / (peaks["vmem_write_gbps"] * 1e9)}
    bound = max(times, key=times.get)
    return times[bound], bound
