"""Plain references: the direct sum the FMM approximates, and one RK2 step.

The vortex velocity in the program's convention, ``W = u - i v``:

    W(z) = sum_j gamma_j / (2 pi i (z - z_j))
                 * (1 - exp(-|z - z_j|^2 / (2 sigma^2)))

with the pair of a particle with itself left out.  ``velocity_f64`` is the
float64 host sum, a copy of ``repro.core.equations.direct_sum`` for the
vortex kernel in real arithmetic; ``velocity_device`` is the same sum in
blocks on the accelerator, in float32 (where every source's velocity is
needed) or in bfloat16 (the control: the reference computed one precision
below the configuration's float32).  The control comes in two variants
(``CONTROLS``): positions, strengths and the pair arithmetic in bfloat16,
or only the pair arithmetic, after float32 differences of float32
positions, as a lower-precision near-field kernel would compute it.
Nothing here reads the program.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from functools import lru_cache, partial

import numpy as np

# host f64 tiles: 16 targets x 8192 sources, 1 MiB a temporary, so that a
# tile stays in cache
TARGET_TILE, SOURCE_TILE = 16, 8192
CONTROL_DTYPE = "bfloat16"      # one below the configurations' float32
# the control's variants: name -> whether differences stay in float32
CONTROLS = {"bfloat16": False, "bfloat16_pairs": True}


def _w_tile(zt, xs, ys, gs, inv2s2):
    tx, ty = zt.real, zt.imag
    u = np.zeros(len(zt))
    v = np.zeros(len(zt))
    for s0 in range(0, len(xs), SOURCE_TILE):
        sl = slice(s0, s0 + SOURCE_TILE)
        dx = tx[:, None] - xs[None, sl]
        dy = ty[:, None] - ys[None, sl]
        r2 = dx * dx + dy * dy
        with np.errstate(divide="ignore", invalid="ignore"):
            a = gs[None, sl] * -np.expm1(-r2 * inv2s2) / r2
        a[r2 == 0] = 0.0                       # a particle and itself
        u -= np.einsum("ij,ij->i", a, dy)
        v += np.einsum("ij,ij->i", a, dx)
    return u - 1j * v


def velocity_f64(z_tgt, z_src, gamma, sigma: float,
                 threads: int | None = None) -> np.ndarray:
    """complex128 ``W`` at each target from every source (host, float64;
    tiles of targets on a few threads)."""
    z_tgt = np.asarray(z_tgt, np.complex128)
    z_src = np.asarray(z_src, np.complex128)
    work = partial(_w_tile, xs=z_src.real.copy(), ys=z_src.imag.copy(),
                   gs=np.asarray(gamma, np.float64) / (2.0 * np.pi),
                   inv2s2=1.0 / (2.0 * sigma * sigma))
    tiles = [z_tgt[s:s + TARGET_TILE]
             for s in range(0, len(z_tgt), TARGET_TILE)]
    with ThreadPoolExecutor(threads or min(8, os.cpu_count() or 1)) as pool:
        parts = list(pool.map(work, tiles))
    return np.concatenate(parts) if parts else np.zeros(0, np.complex128)


def velocity_device(z_tgt, z_src, gamma, sigma: float, dtype="float32",
                    block: int = 256,
                    f32_differences: bool = False) -> np.ndarray:
    """``W`` at each target, summed on the default device in blocks of
    ``block`` targets: positions, strengths and the pair arithmetic in
    ``dtype`` (with ``f32_differences``, positions and their differences
    stay float32 and only what follows is in ``dtype``), sums accumulated
    in float32 (as the chip's units accumulate bfloat16).  Returns
    complex128 on the host."""
    import jax.numpy as jnp
    z_tgt = np.asarray(z_tgt, np.complex128)
    z_src = np.asarray(z_src, np.complex128)
    nt = len(z_tgt)
    pad = (-nt) % block
    zt = np.concatenate([z_tgt, np.full(pad, z_tgt[0])]) if pad else z_tgt
    f32 = np.float32
    u, v = _jitted_sum(dtype, f32_differences)(
        jnp.asarray(zt.real.astype(f32).reshape(-1, block)),
        jnp.asarray(zt.imag.astype(f32).reshape(-1, block)),
        jnp.asarray(z_src.real.astype(f32)),
        jnp.asarray(z_src.imag.astype(f32)),
        jnp.asarray((np.asarray(gamma, np.float64) / (2 * np.pi)).astype(f32)),
        float(1.0 / (2.0 * sigma * sigma)))
    u = np.asarray(u, np.float64).reshape(-1)[:nt]
    v = np.asarray(v, np.float64).reshape(-1)[:nt]
    return u - 1j * v


def _device_sum_impl(xt, yt, xs, ys, gs, inv2s2, dtype, f32_differences):
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    g = gs.astype(dt)[None, :]
    c = jnp.asarray(inv2s2, dt)

    def one(blk):
        bx, by = blk
        if f32_differences:
            dx = (bx[:, None] - xs[None, :]).astype(dt)
            dy = (by[:, None] - ys[None, :]).astype(dt)
        else:
            dx = bx.astype(dt)[:, None] - xs.astype(dt)[None, :]
            dy = by.astype(dt)[:, None] - ys.astype(dt)[None, :]
        r2 = dx * dx + dy * dy
        pos = r2 > 0
        a = jnp.where(pos, g * -jnp.expm1(-r2 * c)
                      / jnp.where(pos, r2, jnp.ones((), dt)),
                      jnp.zeros((), dt))
        u = -jnp.sum(a * dy, axis=1, dtype=jnp.float32)
        v = jnp.sum(a * dx, axis=1, dtype=jnp.float32)
        return u, v
    return jax.lax.map(one, (xt, yt))


@lru_cache(maxsize=None)
def _jitted_sum(dtype: str, f32_differences: bool):
    import jax
    return jax.jit(partial(_device_sum_impl, dtype=dtype,
                           f32_differences=f32_differences),
                   static_argnums=(5,))


def rk2_step(z0, gamma, sigma: float, dt: float, sample, dtype="float32",
             f32_differences: bool = False):
    """Reference positions after one RK2 midpoint step
    (``z_mid = z0 + dt/2 conj(W(z0))``, ``z1 = z0 + dt conj(W(z_mid))``,
    the second sum over every source at its midpoint) for the particles in
    ``sample``.  ``dtype="float32"``: the every-source stage on the device
    in float32, the sampled particles' own stages in float64 on the host.
    A lower ``dtype`` (the control) runs every stage on the device in it,
    with ``f32_differences`` as ``velocity_device`` takes it.
    Returns complex128 ``z1[sample]``."""
    z0 = np.asarray(z0, np.complex128)
    w_all = velocity_device(z0, z0, gamma, sigma, dtype=dtype,
                            f32_differences=f32_differences)
    z_mid = z0 + 0.5 * dt * np.conj(w_all)
    if dtype == "float32":
        w1 = velocity_f64(z0[sample], z0, gamma, sigma)
        z_mid[sample] = z0[sample] + 0.5 * dt * np.conj(w1)
        w2 = velocity_f64(z_mid[sample], z_mid, gamma, sigma)
    else:
        w2 = velocity_device(z_mid[sample], z_mid, gamma, sigma, dtype=dtype,
                             f32_differences=f32_differences)
    return z0[sample] + dt * np.conj(w2)


def rel_l2(a, b) -> float:
    """``||a - b|| / ||b||``; NaN anywhere gives NaN."""
    a = np.asarray(a)
    b = np.asarray(b)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))
