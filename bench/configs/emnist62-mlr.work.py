"""What the emnist62-mlr rounds require of their participants, counted from
shapes: the numerator of ``mfu_roofline.fed``, the same whatever implements
the round."""
from __future__ import annotations

import numpy as np

F32 = 4


def round_work(cfg: dict, counts) -> dict:
    """FLOPs and bytes for the participants whose sample counts are
    ``counts`` (one entry per participant per round).

    Each participant reads its samples (f32 pixels and an i32 label) once,
    computes its gradient (logits and the weight gradient, 2 x 2 x n x
    (pixels + 1) x classes FLOPs), and reads and writes its bank row once.
    """
    pixels, classes = cfg["image_pixels"], cfg["classes"]
    samples, members = int(np.sum(counts)), len(counts)
    params = (pixels + 1) * classes
    flops = 4.0 * samples * (pixels + 1) * classes
    nbytes = samples * (pixels * F32 + 4) + members * 2 * params * F32
    return {"flops": flops, "bytes": float(nbytes)}
