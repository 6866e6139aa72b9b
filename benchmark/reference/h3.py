"""Plain H3 forward snap in NumPy float64: (lat, lng) radians -> cell id.

The benchmark's own reference for the cell a GPS event belongs to.  It
imports nothing of the program under test: the grid constants come from
``h3_tables.json`` beside this file (the public H3 icosahedron: face
centres, Class II axis azimuths, base-cell and pentagon tables), and
the algorithm is the published one, written out step by step:

1. unit vector -> nearest icosahedron face (largest dot product);
2. gnomonic projection onto that face's tangent plane, in Class II
   hex-plane units, rotated by the aperture-7 angle on Class III
   resolutions and scaled by sqrt(7)^res;
3. round to the containing hex (ijk coordinates);
4. walk up the aperture-7 hierarchy, one digit per resolution;
5. base cell and home-orientation rotation from the face tables,
   pentagons skipping the deleted K sub-sequence;
6. pack mode, resolution, base cell and digits into 64 bits.

Everything is float64 and vectorised over events; ints are exact.
"""

from __future__ import annotations

import functools
import json
import math
from pathlib import Path

import numpy as np

K_AXES_DIGIT = 1
# digit rotation tables: CCW / CW by 60 degrees (K->IK, J->JK, ...)
ROTATE60_CCW = np.array([0, 5, 3, 1, 6, 4, 2], np.int64)
ROTATE60_CW = np.array([0, 3, 6, 2, 5, 1, 4], np.int64)
# aperture-7 child-centre images of the i, j, k unit vectors
DOWN_AP7 = ((3, 0, 1), (1, 3, 0), (0, 1, 3))    # counter-clockwise
DOWN_AP7R = ((3, 1, 0), (0, 3, 1), (1, 0, 3))   # clockwise
MODE_CELL = 1


@functools.lru_cache(maxsize=1)
def tables() -> dict:
    with open(Path(__file__).with_name("h3_tables.json"), encoding="utf-8") as fh:
        t = json.load(fh)
    geo = np.asarray(t["FACE_CENTER_GEO"], np.float64)
    clat = np.cos(geo[:, 0])
    face_xyz = np.stack([clat * np.cos(geo[:, 1]), clat * np.sin(geo[:, 1]),
                         np.sin(geo[:, 0])], axis=1)
    # per-face tangent basis: the face's north/east frame turned to its
    # Class II i-axis azimuth, in res-0 hex-plane units
    zhat = np.array([0.0, 0.0, 1.0])
    north = zhat[None, :] - (face_xyz @ zhat)[:, None] * face_xyz
    north /= np.linalg.norm(north, axis=1, keepdims=True)
    east = np.cross(np.broadcast_to(zhat, face_xyz.shape), face_xyz)
    east /= np.linalg.norm(east, axis=1, keepdims=True)
    az = np.asarray(t["FACE_AXES_AZ_CII"], np.float64)[:, None]
    u0 = t["RES0_U_GNOMONIC"]
    return {
        "face_xyz": face_xyz,
        "u1": (np.cos(az) * north + np.sin(az) * east) / u0,
        "u2": (np.sin(az) * north - np.cos(az) * east) / u0,
        "sqrt7": t["M_SQRT7"], "rot": t["M_AP7_ROT_RADS"],
        "sin60": t["M_SIN60"],
        "bc": np.asarray(t["FACE_IJK_BC"], np.int64),
        "bc_rot": np.asarray(t["FACE_IJK_ROT"], np.int64),
        "pent": np.asarray(t["BC_PENT"], bool),
        "cw_offset": np.asarray(t["PENT_CW_OFFSET"], bool),
    }


def _normalize(i, j, k):
    """Fold negative components away, then remove the common minimum."""
    neg = np.minimum(i, 0)
    i, j, k = i - neg, j - neg, k - neg
    neg = np.minimum(j, 0)
    i, j, k = i - neg, j - neg, k - neg
    neg = np.minimum(k, 0)
    i, j, k = i - neg, j - neg, k - neg
    m = np.minimum(np.minimum(i, j), k)
    return i - m, j - m, k - m


def _div7_round(x):
    # round half away from zero of x / 7 (x / 7 is never a half-integer)
    return np.floor_divide(2 * x + 7, 14)


def _hex_round(x, y, sin60: float):
    """Hex-plane point -> ijk of the hexagon that contains it."""
    a1, a2 = np.abs(x), np.abs(y)
    x2 = a2 / sin60
    x1 = a1 + x2 / 2.0
    m1 = np.floor(x1).astype(np.int64)
    m2 = np.floor(x2).astype(np.int64)
    r1, r2 = x1 - m1, x2 - m2
    i = np.where(
        r1 < 0.5,
        np.where(r1 < 1.0 / 3.0, m1,
                 np.where((1.0 - r1 <= r2) & (r2 < 2.0 * r1), m1 + 1, m1)),
        np.where(r1 < 2.0 / 3.0,
                 np.where((2.0 * r1 - 1.0 < r2) & (r2 < 1.0 - r1), m1, m1 + 1),
                 m1 + 1))
    j = np.where(
        r1 < 0.5,
        np.where(r1 < 1.0 / 3.0, np.where(r2 < (1.0 + r1) / 2.0, m2, m2 + 1),
                 np.where(r2 < 1.0 - r1, m2, m2 + 1)),
        np.where(r1 < 2.0 / 3.0, np.where(r2 < 1.0 - r1, m2, m2 + 1),
                 np.where(r2 < r1 / 2.0, m2, m2 + 1)))
    # mirror into the quadrant of the original point
    even = (j % 2) == 0
    axis_i = np.where(even, j // 2, (j + 1) // 2)
    diff = i - axis_i
    i = np.where(x < 0.0, np.where(even, i - 2 * diff, i - (2 * diff + 1)), i)
    i = np.where(y < 0.0, i - (2 * j + 1) // 2, i)
    j = np.where(y < 0.0, -j, j)
    return _normalize(i, j, np.zeros_like(i))


def _up(i, j, k, class_iii: bool):
    """Parent ijk one resolution up, and the centre of that parent's
    child at this resolution (to read the digit from)."""
    ii, jj = i - k, j - k
    if class_iii:
        pi, pj = _div7_round(3 * ii - jj), _div7_round(ii + 2 * jj)
        vecs = DOWN_AP7
    else:
        pi, pj = _div7_round(2 * ii + jj), _div7_round(3 * jj - ii)
        vecs = DOWN_AP7R
    pi, pj, pk = _normalize(pi, pj, np.zeros_like(pi))
    iv, jv, kv = vecs
    ci, cj, ck = _normalize(pi * iv[0] + pj * jv[0] + pk * kv[0],
                            pi * iv[1] + pj * jv[1] + pk * kv[1],
                            pi * iv[2] + pj * jv[2] + pk * kv[2])
    return (pi, pj, pk), (ci, cj, ck)


def _lead(digits):
    nz = digits != 0
    idx = np.argmax(nz, axis=1)
    lead = np.take_along_axis(digits, idx[:, None], axis=1)[:, 0]
    return np.where(nz.any(axis=1), lead, 0)


def latlng_to_cell(lat_rad, lng_rad, res: int) -> np.ndarray:
    """(N,) radians -> (N,) uint64 H3 cell ids at ``res`` (1..15)."""
    T = tables()
    lat = np.asarray(lat_rad, np.float64)
    lng = np.asarray(lng_rad, np.float64)
    clat = np.cos(lat)
    v = np.stack([clat * np.cos(lng), clat * np.sin(lng), np.sin(lat)], 1)
    dots = v @ T["face_xyz"].T
    face = np.argmax(dots, axis=1)
    p = v / dots[np.arange(len(face)), face][:, None] - T["face_xyz"][face]
    x = np.sum(p * T["u1"][face], axis=1)
    y = np.sum(p * T["u2"][face], axis=1)
    if res % 2 == 1:
        cr, sr = math.cos(T["rot"]), math.sin(T["rot"])
        x, y = x * cr + y * sr, y * cr - x * sr
    scale = T["sqrt7"] ** res
    i, j, k = _hex_round(x * scale, y * scale, T["sin60"])

    digits = np.zeros((len(face), res), np.int64)
    for r in range(res, 0, -1):
        (pi, pj, pk), (ci, cj, ck) = _up(i, j, k, class_iii=r % 2 == 1)
        di, dj, dk = _normalize(i - ci, j - cj, k - ck)
        digits[:, r - 1] = 4 * di + 2 * dj + dk
        i, j, k = pi, pj, pk
    i, j, k = np.clip(i, 0, 2), np.clip(j, 0, 2), np.clip(k, 0, 2)

    bc = T["bc"][face, i, j, k]
    rot = T["bc_rot"][face, i, j, k]
    pent = T["pent"][bc]
    cw_off = T["cw_offset"][bc, face]
    # pentagon: a leading K digit is rotated out, cw or ccw by face side
    k_lead = pent & (_lead(digits) == K_AXES_DIGIT)
    turned = np.where(cw_off[:, None], ROTATE60_CW[digits],
                      ROTATE60_CCW[digits])
    digits = np.where(k_lead[:, None], turned, digits)
    for t in range(5):  # home orientation: rot x 60 degrees ccw
        d1 = ROTATE60_CCW[digits]
        fix = pent & (_lead(d1) == K_AXES_DIGIT)
        d1 = np.where(fix[:, None], ROTATE60_CCW[d1], d1)
        digits = np.where((rot > t)[:, None], d1, digits)

    h = ((np.uint64(MODE_CELL) << np.uint64(59))
         | (np.uint64(res) << np.uint64(52))
         | (bc.astype(np.uint64) << np.uint64(45)))
    for r in range(1, 16):
        d = (digits[:, r - 1].astype(np.uint64) if r <= res
             else np.uint64(7))
        h = h | (d << np.uint64(3 * (15 - r)))
    return h


def snap(lat_rad, lng_rad, res: int, block: int = 1 << 17,
         threads: int = 8) -> np.ndarray:
    """``latlng_to_cell`` in blocks of ``block`` events (bounded memory),
    spread over ``threads`` threads (NumPy releases the GIL)."""
    from concurrent.futures import ThreadPoolExecutor

    n = len(lat_rad)
    out = np.empty(n, np.uint64)

    def one(s: int) -> None:
        out[s:s + block] = latlng_to_cell(lat_rad[s:s + block],
                                          lng_rad[s:s + block], res)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        for f in [pool.submit(one, s) for s in range(0, n, block)]:
            f.result()
    return out
