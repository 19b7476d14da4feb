"""Arithmetic shared by the metric readers: the operation and byte
counts of the model and its kernels at the algorithm's shapes.

Counts are of the work the algorithm needs, not of what a kernel pads
to: the decision kernel at (slots B, classes N, samples per round R,
rank 16), not at the 128 lanes it pads N to.  Bytes are float32 (4
bytes) per element read or written once.
"""

from __future__ import annotations

F32 = 4


def decision_kernel_cost(b: int, n: int, r: int) -> tuple:
    """(flops, bytes) of one round: the [R,16]x[16,N] mixing per slot
    plus 8 elementwise operations per logit sample (affine, max, exp,
    sum, normalize, log, p*logp, p*p)."""
    flops = 2 * r * b * n * 16 + 8 * r * b * n
    elems = (2 * b * n + b * n * 16 + r * b * 16 + b      # y_mu, x_sigma, m,
             + 2 * b * n + 2 * b)                           # sel, mask; out
    return flops, elems * F32


def trunk_layers(image: int, channels, kernel: int, stride: int = 2):
    """[(rows per image, depth K, outputs N)] of the trunk's convs."""
    out, size, c_in = [], image, 1
    for c_out in channels:
        size = (size - kernel) // stride + 1
        out.append((size * size, kernel * kernel * c_in, c_out))
        c_in = c_out
    return out


def trunk_flops(image: int, channels, kernel: int) -> int:
    return sum(2 * m * k * n for m, k, n in
               trunk_layers(image, channels, kernel))


def head_flops(d_in: int, n: int) -> int:
    """Activation basis per decision: y_mu, x_sigma, the 16 basis
    products."""
    return 2 * d_in * n * (2 + 16)


def roofline_share(calls: int, flops: int, byts: int, seconds: float,
                   peaks: dict) -> tuple | None:
    """(share %, binding bound) of ``calls`` calls that took ``seconds``
    of device time; None when the trace holds no such call."""
    if not calls or seconds <= 0:
        return None
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = byts / peaks["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    return 100.0 * calls * max(t_flops, t_bytes) / seconds, bound
