"""Share of the chip's peak HBM bandwidth that reading the weights
alone accounts for in a decode micro-step: ``bytes_model.
decode_weight_bytes`` at the window's ``decode_batch_mean`` (for a
mixture of experts the experts that many sequences reach between them:
all 8 at a batch of 31, so a grouped kernel has nothing to skip there)
over ``decode_step_ms`` times the peak from ``peaks.json``.  Bytes a
step must read over a measured device time cannot pass 100%; a reading
above it means the time is wrong, and the run fails.  Layer: kernels.
Moves ``tpot_p50_ms``."""

from benchmark import bytes_model
from benchmark.layer_metrics import decode_batch_mean, decode_step_ms


def read(ctx: dict):
    step_ms = decode_step_ms.read(ctx)
    batch = decode_batch_mean.read(ctx)
    if step_ms is None or batch is None:
        return None
    need = bytes_model.decode_weight_bytes(ctx["config"], batch)
    pct = 100.0 * need / (step_ms / 1e3) / ctx["peak"]["hbm_bytes_per_s"]
    if pct > 100.0:
        raise ValueError(
            f"decode_weight_bw_pct reads {pct:.1f}%: {need:.0f} bytes in "
            f"{step_ms:.3f} ms is above the chip's peak; the program's "
            "device time is wrong")
    return pct
