"""``kernels.flash_decode_roofline`` for the cells that report
``serve_tpot_mean_ms``."""

from benchmarks import common


def read(run):
    return common.load_file("layer_metrics",
                            "kernels.flash_decode_roofline").read(run)
