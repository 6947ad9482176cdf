"""Generate native/cavlc_tables.h from the FFmpeg-validated Python tables.

Single source of truth: selkies_tpu/models/h264/tables.py. Regenerate with:
    PYTHONPATH=. python tools/gen_cavlc_tables.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from selkies_tpu.models.h264 import tables as T


def vlc_rows(table, ncols):
    out = []
    for row in table:
        cells = []
        for t1 in range(ncols):
            c = row[t1] if t1 < len(row) else None
            if c is None:
                cells.append("{0, 0}")
            else:
                val, n = T._vlc(c) if isinstance(c, str) else c
                cells.append(f"{{{val}, {n}}}")
        out.append("  {" + ", ".join(cells) + "}")
    return ",\n".join(out)


def main() -> None:
    lines = []
    lines.append("// AUTO-GENERATED by tools/gen_cavlc_tables.py — DO NOT EDIT.")
    lines.append("// Source of truth: selkies_tpu/models/h264/tables.py (FFmpeg-validated).")
    lines.append("#pragma once")
    lines.append("#include <cstdint>")
    lines.append("")
    lines.append("struct Vlc { uint16_t val; uint8_t len; };")
    lines.append("")
    for name, tab in (
        ("kCoeffTokenNc0", T.COEFF_TOKEN_NC_0_2),
        ("kCoeffTokenNc2", T.COEFF_TOKEN_NC_2_4),
        ("kCoeffTokenNc4", T.COEFF_TOKEN_NC_4_8),
    ):
        lines.append(f"static const Vlc {name}[17][4] = {{")
        lines.append(vlc_rows(tab, 4))
        lines.append("};")
        lines.append("")
    lines.append("static const Vlc kCoeffTokenChromaDc[5][4] = {")
    lines.append(vlc_rows(T.COEFF_TOKEN_CHROMA_DC, 4))
    lines.append("};")
    lines.append("")
    lines.append("// total_zeros, 4x4 (rows: TotalCoeff-1; cols: total_zeros)")
    lines.append("static const Vlc kTotalZeros4x4[15][16] = {")
    lines.append(vlc_rows(T.TOTAL_ZEROS_4x4, 16))
    lines.append("};")
    lines.append("")
    lines.append("static const Vlc kTotalZerosChromaDc[3][4] = {")
    lines.append(vlc_rows(T.TOTAL_ZEROS_CHROMA_DC, 4))
    lines.append("};")
    lines.append("")
    lines.append("// run_before (rows: min(zerosLeft,7)-1; cols: run 0..6)")
    lines.append("static const Vlc kRunBefore[7][7] = {")
    lines.append(vlc_rows(T.RUN_BEFORE, 7))
    lines.append("};")
    lines.append("")
    zz = ", ".join(str(int(i)) for i in T.ZIGZAG_FLAT)
    lines.append(f"static const int kZigzag[16] = {{{zz}}};")
    lb = ", ".join(f"{{{x}, {y}}}" for x, y in T.LUMA_BLOCK_ORDER)
    lines.append(f"static const int kLumaBlockOrder[16][2] = {{{lb}}};  // (x4, y4)")
    cb = ", ".join(f"{{{x}, {y}}}" for x, y in T.CHROMA_BLOCK_ORDER)
    lines.append(f"static const int kChromaBlockOrder[4][2] = {{{cb}}};")
    lines.append("")
    out = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native", "cavlc_tables.h")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
