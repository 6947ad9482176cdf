"""A cell of BENCHMARK.json cut to a size the CPU backend runs in seconds:
its mix, limits and metrics as committed, its configuration at 256x144
and 8 fps (slow enough that the CPU never skips a tick, so warm-up sees
every executable the window uses)."""

from benchmark import run

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 4242


def tiny_cell(name: str = "h264-1080p60.game") -> run.Cell:
    cell = run.load_cell(name)
    cell.config = dict(cell.config, width=256, height=144, framerate=8,
                       video_bitrate_kbps=400)
    return cell
