"""Roofline share of the Pallas ME kernel (models/h264/pallas_me.py).

Work is counted from the algorithm's shapes, not the kernel's grid, so
the count reads the same whatever implements ME: per call, every MB of
the padded frame tests every refine candidate (the zero MV plus TOPK
coarse winners x (2*REFINE_R+1)^2 full-pel offsets) with a 16x16 SAD of
3 operations a pixel (subtract, absolute, accumulate), then predicts the
winner: luma is a copy, chroma a bilinear blend of 8 operations per
sample. Bytes: each plane read once and each output written once, one
byte a sample (the uint8 planes the algorithm works on): current luma,
reference luma and chroma, predicted luma and chroma, and two int16 MV
components per MB. The least time is the larger of ops / peak ops
(bf16 rate from benchmark/peaks.json) and bytes / peak HBM bandwidth; at
1080p the bytes bound it (about 8.4 MB against 0.48 G operations).
Share = least time x calls / device time of the kernel's events. On a
v5e trace the kernel's op is named after the jitted call that wraps it,
``_me_mc_call`` (with the call's input padding, where XLA keeps that
inside the same op), so the share can read low, never high.
"""

KERNEL = "_me_mc_call"
TOPK, REFINE_R = 3, 2
CANDIDATES = 1 + TOPK * (2 * REFINE_R + 1) ** 2
SAD_OPS_PER_PIXEL = 3
CHROMA_OPS_PER_SAMPLE = 8


def work(width: int, height: int) -> tuple[float, float]:
    """(operations, bytes) of one full-frame ME+MC call."""
    ph, pw = -(-height // 16) * 16, -(-width // 16) * 16
    mbs = (ph // 16) * (pw // 16)
    ops = mbs * CANDIDATES * 256 * SAD_OPS_PER_PIXEL \
        + mbs * 2 * 64 * CHROMA_OPS_PER_SAMPLE
    luma, chroma = ph * pw, ph * pw // 2
    nbytes = (luma            # current luma
              + luma + chroma  # reference planes
              + luma + chroma  # predictions
              + mbs * 2 * 2)   # motion vectors
    return float(ops), float(nbytes)


def bound(width: int, height: int, peaks: dict) -> tuple[str, float]:
    """(bounding resource, least seconds) of one call."""
    ops, nbytes = work(width, height)
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return ("hbm_bytes", t_bytes) if t_bytes >= t_ops else ("ops", t_ops)


def read(run):
    if run.trace is None:
        return None
    names = [k for k in run.trace.op_s if KERNEL in k]
    secs = sum(run.trace.op_s[k] for k in names)
    calls = sum(run.trace.kernel_calls[k] for k in names)
    if secs <= 0 or calls == 0:
        return None
    _, least = bound(run.width, run.height, run.peaks)
    return 100.0 * least * calls / secs
