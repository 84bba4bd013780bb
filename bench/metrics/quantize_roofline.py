"""quantize_roofline: the fused EF + int8 quantize kernel's least time
over its device time, in percent. Least time per call is the longest of
its VMEM loads, its VMEM stores and its HBM bytes, each at the device's
peak (`flops.least_seconds`): the bytes from the call's tile shape, the
share in HBM from where the compiled program placed each operand and
result. Where XLA keeps a bucket in VMEM (dcgan32's 2.6 MB streams), the
stores bound it; where the bucket sits in HBM, HBM does."""
import flops


def read(ctx):
    tr = ctx["trace"]
    w0, w1 = tr["window_ns"]
    least = spent = 0.0
    for _, name, start, dur in tr["ops"]:
        h = tr["hlo"].get(name, {})
        if "quantize_ef" not in h.get("kernel", "") or not h.get("shape"):
            continue
        if start < w0 or start + dur > w1:
            continue
        t, _ = flops.least_seconds(*h["shape"], h["spaces_in"],
                                   h["spaces_out"], ctx["peaks"])
        least += t
        spent += dur * 1e-9
    return 100.0 * least / spent if spent > 0 else None
