"""Generate selkies_tpu/models/h264/cabac_tables.py from system codec libraries.

The CABAC context-initialization tables (ITU-T H.264 tables 9-12..9-33:
1024 contexts x 4 init sets of (m, n) int8 pairs), the LPS range table
(table 9-44) and the LPS state-transition table (table 9-45) are ~2.5k
values that cannot be retyped reliably. Both libx264 and libavcodec ship
them verbatim in .rodata; this tool locates them by byte signature,
cross-validates the two independent sources against each other and
against known spec anchor rows, and emits a checked-in Python module.

Regenerate with:
    PYTHONPATH=. python tools/gen_cabac_tables.py

Layout facts this extraction relies on (verified against both libraries):
  * the four init tables are consecutive [1024][2] int8 blobs at a
    2048-byte stride in the order PB[0], PB[1], PB[2], I — the I table
    is LAST.  Contexts 0..10 are slice-type independent, so the ctx0-10
    signature matches all four tables; the I table is identified
    structurally by its (0,0) placeholder rows at ctx 11..23 (P/B-only
    contexts that table 9-12 does not define);
  * x264 stores rangeTabLPS immediately before its init tables as 64
    rows of 4 bytes in REVERSED state order (state 63 first);
  * x264 stores its transition table before that as
    x264_cabac_transition[128][2] over composite states
    cs = 2*(63 - pStateIdx) + valMPS, from which the spec transIdxLPS
    is recovered (MPS transitions are checked to be min(s+1, 62)).
"""

from __future__ import annotations

import glob
import os
import sys

# First 11 (m, n) pairs of the I-slice init table (ctx 0..10) — enough
# entropy to be unique in a multi-MB shared library.
_SIG = bytes(bytearray([
    20, 241, 2, 54, 3, 74, 20, 241, 2, 54, 3, 74,
    228, 127, 233, 104, 250, 53, 255, 54, 7, 51,
]))
_NCTX = 1024
_TBL = 2 * _NCTX  # bytes per init table

# Spec anchor rows (table 9-44) used to validate the rangeTabLPS blob.
_LPS_ANCHORS = {
    0: (128, 176, 208, 240),
    1: (128, 167, 197, 227),
    2: (128, 158, 187, 216),
    3: (123, 150, 178, 205),
    62: (6, 7, 8, 9),
    63: (2, 2, 2, 2),
}


def _find_candidates() -> list[str]:
    pats = [
        "/usr/lib/x86_64-linux-gnu/libx264.so*",
        "/usr/lib/x86_64-linux-gnu/libavcodec.so*",
        "/usr/lib/*/libx264.so*",
        "/usr/lib/*/libavcodec.so*",
        "/usr/lib/libx264.so*", "/usr/lib/libavcodec.so*",
    ]
    out = []
    for p in pats:
        for f in sorted(glob.glob(p)):
            if os.path.isfile(f) and not os.path.islink(f) and f not in out:
                out.append(f)
    return out


def _extract_init(path: str) -> tuple[int, list[list[tuple[int, int]]]] | None:
    data = open(path, "rb").read()
    off = data.find(_SIG)
    if off < 0:
        return None
    # four consecutive tables in storage order PB[0], PB[1], PB[2], I
    # (the ctx0-10 signature matches all four; find() lands on PB[0])
    raw = []
    for k in range(4):
        base = off + k * _TBL
        blob = data[base:base + _TBL]
        if len(blob) != _TBL:
            return None
        rows = []
        for i in range(_NCTX):
            m = blob[2 * i]
            n = blob[2 * i + 1]
            rows.append((m - 256 if m > 127 else m, n - 256 if n > 127 else n))
        raw.append(rows)
    # sanity: the four tables must share ctx 0..2 (those contexts are
    # slice-type independent in the spec)
    for k in range(1, 4):
        if raw[k][:3] != raw[0][:3]:
            return None
    # identify the I table structurally: ctx 11..23 are P/B-only, so
    # table 9-12 leaves them as (0,0) placeholders; the PB tables have
    # real (m, n) values there.
    def _is_i(rows):
        return all(rows[c] == (0, 0) for c in range(11, 24))
    i_idx = [k for k in range(4) if _is_i(raw[k])]
    if i_idx != [3]:
        return None  # layout hypothesis violated
    tabs = [raw[3], raw[0], raw[1], raw[2]]  # I, PB[0], PB[1], PB[2]
    return off, tabs


def _extract_x264_engine(path: str, init_off: int):
    """rangeTabLPS + transIdxLPS from the blobs preceding x264's init
    tables. Returns (range_lps[64][4], trans_lps[64]) or None."""
    data = open(path, "rb").read()
    if init_off < 512:
        return None
    lps_rev = data[init_off - 256:init_off]
    trans = data[init_off - 512:init_off - 256]
    range_lps = [list(lps_rev[4 * (63 - s):4 * (63 - s) + 4]) for s in range(64)]
    for s, row in _LPS_ANCHORS.items():
        if tuple(range_lps[s]) != row:
            return None
    # composite-state transition blob -> spec transIdxLPS; MPS moves
    # must decode to min(s+1, 62) or the layout hypothesis is wrong.
    trans_lps = []
    for s in range(64):
        cs = 2 * (63 - s)
        mps_next = trans[2 * cs]
        if s < 63 and (63 - (mps_next >> 1) != min(s + 1, 62) or (mps_next & 1) != 0):
            return None
        lps_next = trans[2 * cs + 1]
        trans_lps.append(63 - (lps_next >> 1))
    if trans_lps[63] != 63 or trans_lps[0] != 0:
        return None
    return range_lps, trans_lps


def _fmt_pairs(rows: list[tuple[int, int]]) -> str:
    out, line = [], "    "
    for m, n in rows:
        cell = f"({m},{n}),"
        if len(line) + len(cell) > 78:
            out.append(line)
            line = "    "
        line += cell
    out.append(line)
    return "\n".join(out)


def _fmt_ints(vals, per=16) -> str:
    out = []
    for i in range(0, len(vals), per):
        out.append("    " + ",".join(str(v) for v in vals[i:i + per]) + ",")
    return "\n".join(out)


def main() -> None:
    inits = {}
    engine = None
    for path in _find_candidates():
        got = _extract_init(path)
        if got is None:
            continue
        off, tabs = got
        inits[path] = tabs
        if "x264" in os.path.basename(path) and engine is None:
            engine = _extract_x264_engine(path, off)
    if not inits:
        sys.exit("no codec library with CABAC init tables found")
    if engine is None:
        sys.exit("rangeTabLPS/transIdxLPS not recovered from libx264")
    sources = sorted(inits)
    ref = inits[sources[0]]
    for p in sources[1:]:
        if inits[p] != ref:
            sys.exit(f"init tables differ between {sources[0]} and {p}")
    range_lps, trans_lps = engine

    lines = [
        '"""AUTO-GENERATED by tools/gen_cabac_tables.py -- DO NOT EDIT.',
        "",
        "H.264 CABAC tables (ITU-T H.264 9.3): context initialization (m, n)",
        "pairs for 1024 contexts x {I, cabac_init_idc 0..2}, rangeTabLPS",
        "(table 9-44) and transIdxLPS (table 9-45). Extracted from and",
        "cross-validated between:",
    ] + [f"    {p}" for p in sources] + [
        '"""',
        "",
        "# fmt: off",
        "N_CTX = 1024",
        "",
    ]
    names = ["INIT_I", "INIT_PB0", "INIT_PB1", "INIT_PB2"]
    for name, tab in zip(names, ref):
        lines.append(f"{name} = (")
        lines.append(_fmt_pairs(tab))
        lines.append(")")
        lines.append("")
    lines.append("INIT_PB = (INIT_PB0, INIT_PB1, INIT_PB2)")
    lines.append("")
    lines.append("# rangeTabLPS[pStateIdx][qCodIRangeIdx]")
    lines.append("RANGE_LPS = (")
    for row in range_lps:
        lines.append("    (" + ",".join(str(v) for v in row) + "),")
    lines.append(")")
    lines.append("")
    lines.append("TRANS_LPS = (")
    lines.append(_fmt_ints(trans_lps))
    lines.append(")")
    lines.append("# fmt: on")

    out = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "selkies_tpu", "models", "h264", "cabac_tables.py")
    with open(out, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    print(f"wrote {out} (sources: {', '.join(sources)})")


if __name__ == "__main__":
    main()
