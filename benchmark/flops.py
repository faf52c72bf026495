"""Operations and bytes against the chip's peaks, for utilization and
roofline shares.

The counts themselves are a model's own and live in its family's file under
``families/`` (``train_flops_per_token``, ``decode_step``,
``flash_attention_train``). They are counted from the algorithm, never from
the compiler's cost analysis: a product of (m, k) by (k, n) is 2 m k n
operations, recomputed work is not counted, and bytes are what the algorithm
has to move once. Each returns a dict with ``flops`` and, where a roofline
needs it, ``bytes``. What no model owns is here: ``roofline_share``.
"""

from __future__ import annotations


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bytes: float) -> dict:
    """The least time the chip could take (the larger of operations over
    the peak rate and bytes over the peak bandwidth) over the time taken,
    in percent, and which of the two bounds it."""
    t_flops, t_bytes = flops / peak_flops, nbytes / peak_bytes
    least = max(t_flops, t_bytes)
    return {
        "pct": 100.0 * least / seconds,
        "bound": "compute" if t_flops >= t_bytes else "memory",
        "least_s": least, "t_flops_s": t_flops, "t_bytes_s": t_bytes,
        "seconds": seconds,
    }
