"""The whole round's share of the chip's roofline: the least time one
round could take, the larger of its counted FLOPs over peak FLOP/s and
its counted HBM bytes over peak bytes/s (counts from shapes, in the
configuration's module), over the measured time per round in the traced
window.  Which bound applies is printed on standard error."""
import sys

from bench.lib.peaks import peak_of


def read(run):
    cell = run.cell
    flops, nbytes = cell.module.round_counts(cell.config, cell.n_clients,
                                             cell.k)
    peak = peak_of(run.device_kind)
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    print(f"step_mfu_pct: {flops:.6g} FLOP and {nbytes:.6g} B per round; "
          f"bound by {'FLOPs' if t_flops >= t_bytes else 'bytes'}",
          file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / (run.window_s / run.rounds)
