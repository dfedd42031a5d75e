"""Seeded per-row choices that a driver and its plain reference both make:
the generator each row's content and crop draw from, and the crop itself.
Benchmark code, not the program's; its bytes are part of every store's key
(``harness.store_path``), so an edit here makes new stores.
"""

import math

import numpy as np

#: RandomResizedCrop's ranges (torchvision defaults, the standard ResNet
#: ImageNet recipe): area share and log aspect ratio.
CROP_SCALE = (0.08, 1.0)
CROP_RATIO = (3.0 / 4.0, 4.0 / 3.0)


def row_rng(seed, row_id, stream):
    """The generator every seeded per-row choice draws from: the store's
    content (stream 0) and the crop (stream 1) of one row."""
    return np.random.default_rng([int(seed) % (1 << 63), int(row_id), stream])


def crop_box(seed, row_id, height, width):
    """``(y, x, h, w, flip)``: RandomResizedCrop with a horizontal flip,
    decided by the seed and the row id alone, so a reference recomputes it."""
    rng = row_rng(seed, row_id, 1)
    area = height * width
    log_lo, log_hi = math.log(CROP_RATIO[0]), math.log(CROP_RATIO[1])
    for _ in range(10):
        target = area * rng.uniform(*CROP_SCALE)
        aspect = math.exp(rng.uniform(log_lo, log_hi))
        w = int(round(math.sqrt(target * aspect)))
        h = int(round(math.sqrt(target / aspect)))
        if 0 < w <= width and 0 < h <= height:
            y = int(rng.integers(0, height - h + 1))
            x = int(rng.integers(0, width - w + 1))
            return y, x, h, w, bool(rng.uniform() < 0.5)
    ratio = width / height
    if ratio < CROP_RATIO[0]:
        w, h = width, int(round(width / CROP_RATIO[0]))
    elif ratio > CROP_RATIO[1]:
        h, w = height, int(round(height * CROP_RATIO[1]))
    else:
        h, w = height, width
    return (height - h) // 2, (width - w) // 2, h, w, bool(rng.uniform() < 0.5)


def crop_resize(image, box, size):
    """Cut ``box`` out of an (H, W, 3) uint8 image, resize it bilinearly to
    ``size`` x ``size`` and flip it when the box says so."""
    import cv2
    y, x, h, w, flip = box
    out = cv2.resize(image[y:y + h, x:x + w], (size, size),
                     interpolation=cv2.INTER_LINEAR)
    return np.ascontiguousarray(out[:, ::-1]) if flip else out
