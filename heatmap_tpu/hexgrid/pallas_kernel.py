"""Pallas TPU kernel for the H3 snap hot path (BASELINE.json: "the
h3.geo_to_h3 UDF becomes a vectorized Pallas kernel").

The snap splits into two stages with very different shapes:

1. **Geometry** (this kernel): lat/lng → unit vector → best-of-20
   icosahedron face → gnomonic hex-plane coords → exact int aperture-7
   digit chain.  All elementwise float/int work over the point lanes — a
   single fused VMEM pass with the 20-face search unrolled against python
   scalar constants (no gathers, nothing Mosaic can't lower).  This is
   ~95% of the snap FLOPs; fusing it keeps every intermediate (9 floats +
   a dozen ints per point) out of HBM.
2. **Tables** (left to XLA): base-cell/rotation lookups from <3 KB int32
   tables + 64-bit packing (device._apply_rotations_packed/_pack_packed).
   Tiny gathers on (N,) lanes that XLA already lowers well.

``latlng_to_cell_pallas`` agrees with the pure-XLA
``device.latlng_to_cell_vec`` on all but boundary-epsilon points (the two
float32 expression trees round differently in the last ulp, so a point
within ~1e-3 grid units of a cell edge — well under GPS noise — may snap
to the adjacent cell; differential-tested to <0.2% disagreement in
tests/test_hexgrid_device.py, and both paths carry the same ~0.4 m f32
boundary tolerance vs the f64 host oracle).  Opt-in via
HEATMAP_H3_IMPL=pallas until benchmarked faster on real hardware
(engine.step reads the flag).

Reference parity: replaces heatmap_stream.py:65-75 (geo_to_h3 UDF applied
per row at :105).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from heatmap_tpu.hexgrid import device as dev
from heatmap_tpu.hexgrid.constants import (
    FACE_CENTER_XYZ,
    M_AP7_ROT_RADS,
    M_SQRT7,
)
from heatmap_tpu.hexgrid.mathlib import is_class_iii

_LANES = 128
_SUBLANES = 8  # f32 min tile height
_BLOCK_ROWS = 64  # rows of 128 lanes per grid step (64*128 = 8192 pts)


@functools.lru_cache(maxsize=1)
def _face_constants():
    """Per-face scalars for the unrolled face loop: center xyz + the two
    tangent-basis vectors (device._projection_bases), as python floats."""
    u1, u2 = dev._projection_bases()
    c = np.asarray(FACE_CENTER_XYZ, np.float64)
    return [tuple(map(float, (c[f, 0], c[f, 1], c[f, 2],
                              u1[f, 0], u1[f, 1], u1[f, 2],
                              u2[f, 0], u2[f, 1], u2[f, 2])))
            for f in range(20)]


def _snap_kernel(lat_ref, lng_ref, face_ref, flat_ref, p_ref, *, res: int):
    f32 = jnp.float32
    lat = lat_ref[:]
    lng = lng_ref[:]
    clat = jnp.cos(lat)
    vx = clat * jnp.cos(lng)
    vy = clat * jnp.sin(lng)
    vz = jnp.sin(lat)

    # best-of-20 face search, fully unrolled against scalar constants;
    # the winning face's basis vectors ride along in the same selects
    best = jnp.full_like(vx, -2.0)
    # derive from the tracer (zeros_like), NOT jnp.zeros(shape): a
    # no-tracer-input op evaluates to a concrete array under an ambient
    # eager context and pallas rejects concrete captures as constants
    face = jnp.zeros_like(vx, dtype=jnp.int32)
    acc = [jnp.zeros_like(vx) for _ in range(9)]
    for f, consts in enumerate(_face_constants()):
        cx, cy, cz = consts[0], consts[1], consts[2]
        d = vx * f32(cx) + vy * f32(cy) + vz * f32(cz)
        m = d > best
        best = jnp.where(m, d, best)
        face = jnp.where(m, f, face)
        acc = [jnp.where(m, f32(consts[t]), acc[t]) for t in range(9)]
    cxv, cyv, czv, u1x, u1y, u1z, u2x, u2y, u2z = acc

    # gnomonic projection onto the winning face's tangent plane
    # (true division, not reciprocal-multiply: must round identically to
    # the XLA path or boundary points snap to a neighboring cell)
    px = vx / best - cxv
    py = vy / best - cyv
    pz = vz / best - czv
    x = px * u1x + py * u1y + pz * u1z
    y = px * u2x + py * u2y + pz * u2z
    if is_class_iii(res):
        cr = f32(math.cos(M_AP7_ROT_RADS))
        sr = f32(math.sin(M_AP7_ROT_RADS))
        x, y = x * cr + y * sr, y * cr - x * sr
    scale = f32(M_SQRT7 ** res)
    x = x * scale
    y = y * scale

    # exact int aperture-7 digit chain (device helpers are pure elementwise)
    i, j, k = dev._hex2d_to_ijk(x, y)
    p = jnp.zeros_like(i)
    for r in range(res, 0, -1):
        last = (i, j, k)
        if is_class_iii(r):
            i, j, k = dev._up_ap7(i, j, k)
            ci, cj, ck = dev._lin3(dev._DOWN_AP7, i, j, k)
        else:
            i, j, k = dev._up_ap7r(i, j, k)
            ci, cj, ck = dev._lin3(dev._DOWN_AP7R, i, j, k)
        di, dj, dk = dev._ijk_normalize(last[0] - ci, last[1] - cj,
                                        last[2] - ck)
        p = p | ((4 * di + 2 * dj + dk) << (3 * (res - r)))

    i = jnp.clip(i, 0, 2)
    j = jnp.clip(j, 0, 2)
    k = jnp.clip(k, 0, 2)
    face_ref[:] = face
    flat_ref[:] = ((face * 3 + i) * 3 + j) * 3 + k
    p_ref[:] = p


@functools.partial(jax.jit, static_argnames=("res", "interpret"))
def _snap_geometry(lat, lng, res: int, interpret: bool = False):
    """(N,) radians -> (face, flat27, packed_digits), N padded internally."""
    n = lat.shape[0]
    block = _BLOCK_ROWS * _LANES
    n_pad = max(-n % block, 0)
    if n_pad:
        lat = jnp.pad(lat, (0, n_pad))
        lng = jnp.pad(lng, (0, n_pad))
    rows = (n + n_pad) // _LANES
    lat2 = lat.reshape(rows, _LANES)
    lng2 = lng.reshape(rows, _LANES)
    grid = (rows // _BLOCK_ROWS,)
    spec = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda r: (r, 0))
    out_shape = jax.ShapeDtypeStruct((rows, _LANES), jnp.int32)
    face, flat, p = pl.pallas_call(
        functools.partial(_snap_kernel, res=res),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=(spec, spec, spec),
        out_shape=(out_shape, out_shape, out_shape),
        interpret=interpret,
    )(lat2, lng2)
    return (face.reshape(-1)[:n], flat.reshape(-1)[:n], p.reshape(-1)[:n])


@functools.partial(jax.jit, static_argnames=("res", "interpret"))
def latlng_to_cell_pallas(lat, lng, res: int, interpret: bool = False):
    """Drop-in float32 equivalent of device.latlng_to_cell_vec (res <= 10):
    Pallas geometry stage + XLA table stage."""
    if not 0 <= res <= 10:
        raise ValueError("pallas snap path supports res 0..10")
    lat = jnp.asarray(lat, jnp.float32)
    lng = jnp.asarray(lng, jnp.float32)
    face, flat, p = _snap_geometry(lat, lng, res, interpret=interpret)
    ijk = ((flat // 9) % 3, (flat // 3) % 3, flat % 3)
    bc, p = dev._apply_rotations_packed(face, ijk, p, res)
    return dev._pack_packed(bc, p, res)


@functools.lru_cache(maxsize=1)
def pallas_available() -> bool:
    """True when the default backend is a TPU and the kernel lowers
    there; False on any other backend.  On a TPU a lowering error
    RAISES: a kernel that should compile and does not is a fault to
    see, never a reason to run the XLA snap in its place.

    The probe must work at trace time (engine._snap_impl runs inside
    the engine's jit) yet actually LOWER the kernel — under an ambient
    trace a plain jitted call is traced, not compiled, so no Mosaic
    error would surface.  AOT ``lower().compile()`` on abstract shapes
    does both: it opens a fresh trace independent of any ambient tracer
    and runs the real backend compile."""
    if jax.default_backend() != "tpu":
        return False
    spec = jax.ShapeDtypeStruct((_LANES * _SUBLANES,), jnp.float32)
    jax.jit(functools.partial(
        latlng_to_cell_pallas, res=8)).lower(spec, spec).compile()
    return True
