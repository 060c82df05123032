"""Shared Pallas kernel-geometry helpers.

`clamp_tiles` is the one home of the tile-clamp + pad arithmetic that
every Pallas wrapper used to copy-paste (`tm = min(tm, M)`,
`pm = (-M) % tm`); `kernels/ops.py` re-exports it for callers outside
the kernel package.  `tiled_bytes` is the one home of the TPU's VMEM
tile padding, which every kernel's `vmem_bytes` model prices blocks at,
and `phase_split` the one home of the stride-phase input layout that
lets kernels read strided windows with unit-stride loads.
``STRIDED_LANES`` is the one home of the lane limit of Mosaic's strided
loads and stores.
"""
from typing import Sequence, Tuple

import jax.numpy as jnp
from jax.experimental.pallas import tpu as _pltpu

CompilerParams = _pltpu.CompilerParams

#: widest lane extent of a block Mosaic's strided loads and stores take
#: (32-bit data only): the fused pool's scratch reads in cuconv_fused,
#: the NHWC output interleave in winograd_pallas
STRIDED_LANES = 128


def clamp_tiles(dims: Sequence[int], tiles: Sequence[int]
                ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Clamp tile sizes to their dims and derive the pad-to-multiple.

    Returns ``(clamped, pads)`` where ``clamped[i] = min(tiles[i],
    dims[i])`` and ``pads[i] = (-dims[i]) % clamped[i]`` — so
    ``dims[i] + pads[i]`` is the padded extent and
    ``(dims[i] + pads[i]) // clamped[i]`` the grid size along that axis.
    Non-positive tile sizes are a caller bug and raise.
    """
    if len(dims) != len(tiles):
        raise ValueError(f"{len(dims)} dims but {len(tiles)} tile sizes")
    clamped, pads = [], []
    for d, t in zip(dims, tiles):
        t = int(t)
        if t < 1:
            raise ValueError(f"tile sizes must be >= 1; got {tiles}")
        t = min(t, int(d))
        clamped.append(t)
        pads.append((-int(d)) % t)
    return tuple(clamped), tuple(pads)


def tiled_bytes(shape: Sequence[int], itemsize: int) -> int:
    """VMEM bytes of one array under the TPU's (sublane, lane) tiling:
    the last dim pads to 128 lanes and the second-last to 8 * (4 //
    itemsize) sublanes, so a 3-channel NHWC row occupies a full
    128-lane tile.  1-D shapes count as one sublane row."""
    shape = tuple(int(d) for d in shape)
    if len(shape) == 1:
        shape = (1,) + shape
    *lead, sub, lane = shape
    sub_tile = 8 * max(1, 4 // itemsize)
    n = -(-sub // sub_tile) * sub_tile * (-(-lane // 128) * 128)
    for d in lead:
        n *= d
    return n * itemsize


def phase_extents(h: int, w: int, kh: int, kw: int,
                  stride: Tuple[int, int], padding: Tuple[int, int],
                  oh: int, ow: int) -> Tuple[int, int]:
    """``(hq, wq)`` for ``phase_split``: per-phase extents covering the
    padded input and every tap of an ``oh x ow`` output grid (``oh``
    may exceed the conv's output rows when a kernel rounds them up to
    whole blocks)."""
    sh, sw = stride
    hp, wp = h + 2 * padding[0], w + 2 * padding[1]
    return (max(-(-hp // sh), oh + (kh - 1) // sh),
            max(-(-wp // sw), ow + (kw - 1) // sw))


def phase_split(x, stride: Tuple[int, int], padding: Tuple[int, int],
                hq: int, wq: int):
    """Zero-pad NHWC ``x`` and split it into stride phases:
    ``out[n, r, h', s, w', c] = xp[n, h'*sh + r, w'*sw + s, c]``, shape
    ``(N, sh, hq, sw, wq, C)``.

    Filter tap ``(i, j)`` of a strided conv then reads phase
    ``(i % sh, j % sw)`` at offset ``(i // sh, j // sw)`` with unit
    stride, so kernels need no strided load (Mosaic's strided loads
    take only 32-bit data on at most 128 lanes).  At stride 1 the split
    is a free reshape; otherwise it costs one XLA transpose of the
    input.  ``hq``/``wq`` come from ``phase_extents``; the extra rows
    and columns are zeros.
    """
    n, h, w, c = x.shape
    sh, sw = stride
    ph, pw = padding
    xp = jnp.pad(x, ((0, 0), (ph, sh * hq - h - ph), (pw, sw * wq - w - pw),
                     (0, 0)))
    return xp.reshape(n, hq, sh, wq, sw, c).transpose(0, 2, 1, 4, 3, 5)
