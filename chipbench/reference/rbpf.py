"""Plain RBPF filter that copies densely, for checking the lazy store.

The model (Lindsten & Schoen 2010, as the paper runs it): a scalar
nonlinear state ``xi`` and a linear-Gaussian ``z in R^2`` marginalised
per particle by a Kalman filter,

    xi_{t+1} = 0.5 xi + 25 xi / (1 + xi^2) + 8 cos(1.2 t) + c.z_t + v,  v ~ N(0, 0.5)
    z_{t+1}  = A z_t + w,   w ~ N(0, 0.1 I)
    y_t      = 0.05 xi_t^2 + b.z_t + e,   e ~ N(0, 0.5)

with A = [[0.8, 0.1], [-0.1, 0.8]], c = (0.3, -0.2), b = (1, 0.5); each
particle carries (xi, m, P) with z | xi, y ~ N(m, P), and its record is
``[xi, m0, m1, P00, P01, P11]``.

:func:`dense_filter` is a bootstrap filter over those equations that
resamples (systematic) at every step after the first and gathers the
whole trajectory array ``[N, T, 6]`` at each resampling: the eager copy
the lazy store avoids.  It draws its randomness in the same order as the
program's filter, so a sound lazy store gives the same trajectories.  Its
products run at the matmul precision the configuration states
(``matmul_precision``; ``default`` is the backend's default, one bfloat16
pass on a TPU, which is how the program's own products run).
:func:`kalman_residual` checks stored trajectories one record at a time:
given a record and the next ``xi``, the next (m, P) follow exactly.
:func:`simulate` makes the observations a run filters.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

A = ((0.8, 0.1), (-0.1, 0.8))
QZ = 0.1
C = (0.3, -0.2)
B = (1.0, 0.5)
Q_XI = 0.5
R_Y = 0.5


def _f(xi, t):
    return 0.5 * xi + 25.0 * xi / (1.0 + xi * xi) + 8.0 * jnp.cos(1.2 * t)


def _mats(dtype):
    return (jnp.array(A, dtype), QZ * jnp.eye(2, dtype=dtype), jnp.array(C, dtype),
            jnp.array(B, dtype))


def _step(key, xi, m, p, t, y, dtype, precision):
    a, qz, c, b = _mats(dtype)
    einsum = functools.partial(jnp.einsum, precision=precision)
    matmul = functools.partial(jnp.matmul, precision=precision)
    k_xi, _ = jax.random.split(key)
    f = _f(xi, t.astype(jnp.float32).astype(dtype))
    mean_xi = f + matmul(m, c)
    var_xi = Q_XI + einsum("i,nij,j->n", c, p, c)
    xi_new = mean_xi + jnp.sqrt(var_xi) * jax.random.normal(k_xi, xi.shape).astype(dtype)
    innov = xi_new - f - matmul(m, c)
    k = einsum("nij,j->ni", p, c) / var_xi[:, None]
    m = m + k * innov[:, None]
    p = p - einsum("ni,nj->nij", k, einsum("nij,j->ni", p, c))
    m = matmul(m, a.T)
    p = einsum("ij,njk,lk->nil", a, p, a) + qz
    y_mean = 0.05 * xi_new * xi_new + matmul(m, b)
    y_var = R_Y + einsum("i,nij,j->n", b, p, b)
    logw = -0.5 * ((y - y_mean) ** 2 / y_var + jnp.log(2 * math.pi * y_var))
    k = einsum("nij,j->ni", p, b) / y_var[:, None]
    m = m + k * (y - y_mean)[:, None]
    p = p - einsum("ni,nj->nij", k, einsum("nij,j->ni", p, b))
    rec = jnp.stack([xi_new, m[:, 0], m[:, 1], p[:, 0, 0], p[:, 0, 1], p[:, 1, 1]], axis=1)
    return xi_new, m, p, logw, rec


def _logsumexp(x):
    m = jnp.max(x)
    return m + jnp.log(jnp.sum(jnp.exp(x - m)))


def _systematic(key, logw):
    n = logw.shape[0]
    w = jnp.exp(logw - _logsumexp(logw))
    u = jax.random.uniform(key)
    positions = (jnp.arange(n) + u) / n
    cum = jnp.cumsum(w)
    cum = cum / cum[-1]
    return jnp.searchsorted(cum, positions, side="left").astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n", "dtype", "precision"))
def dense_filter(key, ys, n: int, dtype: str = "float32", precision: str = "default"):
    """``(log_evidence, trajectories [n, T, 6])``."""
    dt = jnp.dtype(dtype)
    ys = ys.astype(dt)
    steps = ys.shape[0]
    key, k0 = jax.random.split(key)
    xi = jax.random.normal(k0, (n,)).astype(dt)
    m = jnp.zeros((n, 2), dt)
    p = jnp.broadcast_to(jnp.eye(2, dtype=dt), (n, 2, 2))
    traj = jnp.zeros((n, steps, 6), dt)
    logw = jnp.full((n,), -math.log(n), dt)

    def body(carry, t):
        key, xi, m, p, logw, logz, traj = carry
        key, k_res, k_prop, _ = jax.random.split(key, 4)

        def resample(operand):
            xi, m, p, traj, logw = operand
            anc = _systematic(k_res, logw)
            return xi[anc], m[anc], p[anc], traj[anc], jnp.full((n,), -math.log(n), dt)

        xi, m, p, traj, logw = jax.lax.cond(t > 0, resample, lambda o: o,
                                            (xi, m, p, traj, logw))
        xi, m, p, dlogw, rec = _step(k_prop, xi, m, p, t, ys[t], dt, precision)
        lw = logw + dlogw
        logz = logz + _logsumexp(lw)
        logw = lw - _logsumexp(lw)
        traj = traj.at[:, t].set(rec)
        return (key, xi, m, p, logw, logz, traj), None

    carry, _ = jax.lax.scan(body, (key, xi, m, p, logw, jnp.zeros((), dt), traj),
                            jnp.arange(steps))
    return carry[5].astype(jnp.float32), carry[6].astype(jnp.float32)


@jax.jit
def _kalman_next(rec, xi_next, y, t):
    """(m, P) after step ``t`` from the record before it and the new xi,
    in exact float32 arithmetic (no matrix unit)."""
    xi, m0, m1, p00, p01, p11 = (rec[..., i] for i in range(6))
    (a00, a01), (a10, a11) = A
    c0, c1 = C
    b0, b1 = B
    f = _f(xi, t.astype(jnp.float32))
    pc0, pc1 = p00 * c0 + p01 * c1, p01 * c0 + p11 * c1
    var_xi = Q_XI + c0 * pc0 + c1 * pc1
    innov = xi_next - f - (m0 * c0 + m1 * c1)
    k0, k1 = pc0 / var_xi, pc1 / var_xi
    m0, m1 = m0 + k0 * innov, m1 + k1 * innov
    p00, p01, p11 = p00 - k0 * pc0, p01 - k0 * pc1, p11 - k1 * pc1
    m0, m1 = a00 * m0 + a01 * m1, a10 * m0 + a11 * m1
    q00 = a00 * (a00 * p00 + a01 * p01) + a01 * (a00 * p01 + a01 * p11) + QZ
    q01 = a10 * (a00 * p00 + a01 * p01) + a11 * (a00 * p01 + a01 * p11)
    q11 = a10 * (a10 * p00 + a11 * p01) + a11 * (a10 * p01 + a11 * p11) + QZ
    p00, p01, p11 = q00, q01, q11
    y_mean = 0.05 * xi_next * xi_next + b0 * m0 + b1 * m1
    pb0, pb1 = p00 * b0 + p01 * b1, p01 * b0 + p11 * b1
    y_var = R_Y + b0 * pb0 + b1 * pb1
    k0, k1 = pb0 / y_var, pb1 / y_var
    m0, m1 = m0 + k0 * (y - y_mean), m1 + k1 * (y - y_mean)
    p00, p01, p11 = p00 - k0 * pb0, p01 - k0 * pb1, p11 - k1 * pb1
    return jnp.stack([m0, m1, p00, p01, p11], axis=-1)


def kalman_residual(traj, ys) -> float:
    """Largest deviation of stored (m, P) from what the previous record
    and the stored ``xi`` imply, relative to ``1 + |value|``, over every
    particle and step after the first."""
    traj = jnp.asarray(traj, jnp.float32)
    steps = traj.shape[1]
    t = jnp.arange(1, steps)
    want = _kalman_next(traj[:, :-1], traj[:, 1:, 0], jnp.asarray(ys)[1:steps], t)
    got = traj[:, 1:, 1:]
    return float(jnp.max(jnp.abs(got - want) / (1.0 + jnp.abs(want))))


@functools.partial(jax.jit, static_argnames=("steps",))
def simulate(key, steps: int):
    """Observations ``y_{0:T}`` drawn from the model."""
    a, qz, c, b = _mats(jnp.float32)

    def body(carry, t):
        key, xi, z = carry
        key, k1, k2, k3 = jax.random.split(key, 4)
        xi = _f(xi, t.astype(jnp.float32)) + z @ c + math.sqrt(Q_XI) * jax.random.normal(k1)
        z = a @ z + jax.random.multivariate_normal(k2, jnp.zeros(2), qz)
        y = 0.05 * xi * xi + z @ b + math.sqrt(R_Y) * jax.random.normal(k3)
        return (key, xi, z), y

    key, k0 = jax.random.split(key)
    _, ys = jax.lax.scan(body, (key, jax.random.normal(k0), jnp.zeros(2)), jnp.arange(steps))
    return ys

