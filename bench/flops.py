"""Operation and byte counts the per-layer metrics divide by, from shapes.

Model FLOPs of one WGAN field evaluation (`gan_field_fn`), counted as the
two gradients the field needs, with every product that lands on padding or
on the holes of a strided transposed convolution left out:

  L_G:  G forward; D forward on the fakes; D input-gradients on the fakes
        (every layer, down to the image); G weight-gradients and G
        input-gradients (every layer but the first, whose input is z).
  L_D:  D forward on the reals; D input-gradients on the reals (every
        layer but the first); D weight-gradients on the reals and on the
        fakes.

Each product the field needs is counted once. L_D's loss on the fakes is
L_G's negated, so its input-gradients on the fakes are L_G's with the sign
flipped: they are counted on L_G's side alone, as are the forward passes
that L_D repeats (G on z, D on the fakes). Elementwise operations, the
optimizer, error feedback, the quantizer and the exchange do not count. One
FLOP is one multiply or one add: a multiply-accumulate is two.

Bytes of the fused EF + int8 quantize kernel (`kernels/quantize.py`), per
call over an (R, C) tile of a bucket: it reads the message, the residual
and the uniform draws (f32 each), and writes the int8 codes, one f32 scale
per row and the new residual (f32). Every operand is loaded from VMEM and
every result stored there; those the compiler placed in HBM also cross
HBM. The least time of a call is the longest of the three streams at its
peak.
"""
from __future__ import annotations

KERNEL = 4   # the repo's conv helpers fix 4x4 kernels, stride 2, SAME
STRIDE = 2


def conv_taps(h_in: int, k: int = KERNEL, s: int = STRIDE) -> int:
    """Products along one spatial axis of a stride-`s` SAME convolution of
    an axis of length `h_in`, summed over output positions, counting only
    taps that land inside the input."""
    out = -(-h_in // s)
    lo = max((out - 1) * s + k - h_in, 0) // 2
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * s + t - lo < h_in)


def conv_t_taps(h_in: int, k: int = KERNEL, s: int = STRIDE) -> int:
    """The same for a stride-`s` SAME transposed convolution (output
    `h_in * s`), which `lax.conv_transpose` runs over the input dilated by
    `s` and padded by `pad_a` in front: taps on a hole or on padding do
    not count."""
    pad_len = k + s - 2
    pad_a = k - 1 if s > k - 1 else -(-pad_len // 2)
    dilated = (h_in - 1) * s + 1
    return sum(1 for o in range(h_in * s) for t in range(k)
               if 0 <= o + t - pad_a < dilated and (o + t - pad_a) % s == 0)


def dcgan_layer_macs(gc: dict):
    """Multiply-accumulates per image of each layer, in forward order:
    (generator [fc, c1, c2, c3], critic [c1, c2, c3, fc])."""
    bw, img, ch, lat = (gc["base_width"], gc["image_size"], gc["channels"],
                        gc["latent_dim"])
    s0 = img // 8
    gen = [lat * s0 * s0 * bw * 4]
    h, cin = s0, bw * 4
    for cout in (bw * 2, bw, ch):
        gen.append(conv_t_taps(h) ** 2 * cin * cout)
        h, cin = h * 2, cout
    disc = []
    h, cin = img, ch
    for cout in (bw, bw * 2, bw * 4):
        disc.append(conv_taps(h) ** 2 * cin * cout)
        h, cin = -(-h // 2), cout
    disc.append(s0 * s0 * bw * 4)
    return gen, disc


def field_flops_per_image(gc: dict) -> int:
    """Model FLOPs of the WGAN field for one image (see the module doc)."""
    gen, disc = dcgan_layer_macs(gc)
    g_fwd, d_fwd = sum(gen), sum(disc)
    g_bwd = sum(gen) + sum(gen[1:])            # dW all, dX all but first
    d_dx_all = sum(disc)
    d_dw, d_dx_inner = sum(disc), sum(disc[1:])  # dW all; dX all but first
    macs = (g_fwd + d_fwd + d_dx_all + g_bwd     # L_G
            + d_fwd + d_dx_inner + 2 * d_dw)     # L_D: reals; dW on fakes
    return 2 * macs


def step_flops(gc: dict, batch_per_worker: int, workers: int) -> int:
    """Model FLOPs of one training step over all workers."""
    return field_flops_per_image(gc) * batch_per_worker * workers


def n_params(gc: dict) -> int:
    bw, s0 = gc["base_width"], gc["image_size"] // 8
    kk = KERNEL * KERNEL
    w = (gc["latent_dim"] * s0 * s0 * bw * 4
         + kk * (bw * 4 * bw * 2 + bw * 2 * bw + bw * gc["channels"])
         + kk * (gc["channels"] * bw + bw * bw * 2 + bw * 2 * bw * 4)
         + s0 * s0 * bw * 4)
    b = (s0 * s0 * bw * 4 + bw * 2 + bw + gc["channels"]
         + bw + bw * 2 + bw * 4 + 1)
    return w + b


def quantize_kernel_arrays(rows: int, cols: int):
    """Bytes of each operand (message, residual, uniforms) and each result
    (codes, scales, new residual) of one fused EF + int8 quantize call over
    (rows, cols), in the kernel's order."""
    n = rows * cols
    return [4 * n, 4 * n, 4 * n], [n, 4 * rows, 4 * n]


def dcgan_param_sizes(gc: dict):
    """Element counts of the parameter leaves in `jax.tree.flatten` order
    (dicts by sorted key: disc before gen; within a layer b before w)."""
    bw, s0, ch, lat = (gc["base_width"], gc["image_size"] // 8,
                       gc["channels"], gc["latent_dim"])
    kk = KERNEL * KERNEL
    disc = [("c1", bw, kk * ch * bw), ("c2", bw * 2, kk * bw * bw * 2),
            ("c3", bw * 4, kk * bw * 2 * bw * 4), ("fc", 1, s0 * s0 * bw * 4)]
    gen = [("c1", bw * 2, kk * bw * 4 * bw * 2), ("c2", bw, kk * bw * 2 * bw),
           ("c3", ch, kk * bw * ch), ("fc", s0 * s0 * bw * 4,
                                      lat * s0 * s0 * bw * 4)]
    return [n for _, b, w in disc + gen for n in (b, w)]


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
