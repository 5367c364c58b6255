"""The selective scan of a state-space mixer (``models/hybrid_ssm.py``).

For every row and channel ``d`` of ``d_inner``, with a state ``s [N]`` in
float32 whatever the inputs' type:

    dt_t = softplus(delta_t + dt_bias)            (0 at a pad position)
    s_t  = exp(dt_t * A) * s_{t-1} + (dt_t * u_t) * B_t
    m_t  = s_t . C_t + D * u_t
    y_t  = m_t * silu(z_t)

(``ungated``, static: ``m`` is returned beside ``y``, for a family whose
later layers read a scan's output from in front of its gate.)

``A [N, d_inner]`` is negative, ``B_t`` and ``C_t [N]`` are shared by a row's
channels, and ``s_{-1}`` is the state handed in. Every array here has the
CHANNEL axis last (a state is ``[N, d_inner]``, the convolution's history
``[K - 1, d_inner]``): the device tiles the last axis by 128 lanes, and a
last axis of 16 states or 3 taps would be padded to 128 wherever it lies. A pad position (index <
``start[row]``: the engine left-pads) has ``dt = 0``, so the state passes it
unchanged: ``exp(0) = 1`` and ``0 * u * B = 0``.

Two forms, one rule (``selective_scan`` chooses):

- ``selective_scan_xla``: a ``lax.scan`` over time, plain; the oracle, the
  CPU's form and the form of a call of few positions. It can also leave the
  state after EVERY position (a verify step, whose caller keeps some of the
  positions it fed and must be able to go back to the state behind them).
- the kernel ``selective_scan`` (``pl.pallas_call(name=...)``): one row and
  1024 channels a grid point (one float32 register, 8 sublanes by 128 lanes:
  channels fill the register, so no step reduces across lanes), the ``N``
  states of those channels carried in registers over a chunk of time, time
  walked in chunks that Pallas double-buffers from HBM, ``B_t`` and ``C_t``
  read as scalars from SMEM. Per position and state: one ``exp``, five
  multiplies, two adds; nothing ``[S, d_inner, N]`` exists anywhere. It is
  bound by the vector and transcendental units, not the MXU or HBM.

``causal_conv`` is the depthwise causal convolution in front
of the scan, kept here because its state (the last ``d_conv - 1`` inputs)
rides the cache beside the scan's.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL = "selective_scan"  # what a trace calls the kernel
LANES, SUBLANES = 128, 8
TILE = LANES * SUBLANES  # channels a grid point holds: one float32 register
TIME_CHUNK = 256  # positions a grid step walks: 4 blocks of [256, 8, 128], two buffers each
UNROLL = 4  # positions a trip of the kernel's loop writes out (my chip runs, PR 40: 12.0 ms a batch-8 layer against 12.9 at 2 and 15.0 at 1)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def selective_scan_xla(u, delta, z, A, B, C, D, dt_bias, h0, start, *, keep_steps: bool = False,
                       ungated: bool = False):
    """The rule above by a ``lax.scan`` over time. ``u``, ``delta``, ``z``
    ``[R, S, Di]``; ``A [N, Di]``; ``B``, ``C`` ``[R, S, N]``; ``D``,
    ``dt_bias`` ``[Di]``; ``h0 [R, N, Di]``; ``start [R]``. Returns ``y [R, S,
    Di]`` in ``u``'s type, the last state ``[R, N, Di]`` float32, under
    ``keep_steps`` the state after every position ``[R, S, N, Di]`` and, last,
    under ``ungated`` ``m [R, S, Di]`` in ``u``'s type."""
    f32 = jnp.float32
    S = u.shape[1]
    live = jnp.arange(S, dtype=jnp.int32)[None, :] >= start[:, None]  # [R, S]
    dt = jnp.where(live[..., None], _softplus(delta.astype(f32) + dt_bias.astype(f32)), 0.0)
    uf = u.astype(f32)

    def step(h, xs):
        dt_t, u_t, b_t, c_t = xs  # [R, Di], [R, Di], [R, N], [R, N]
        h = jnp.exp(dt_t[:, None, :] * A.astype(f32)) * h + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
        y = jnp.sum(h * c_t[:, :, None], axis=1)
        return h, ((y, h) if keep_steps else y)

    over_time = tuple(a.swapaxes(0, 1) for a in (dt, uf, B.astype(f32), C.astype(f32)))
    last, out = jax.lax.scan(step, h0.astype(f32), over_time)
    y, steps = out if keep_steps else (out, None)
    zf = z.astype(f32)
    m = y.swapaxes(0, 1) + D.astype(f32) * uf
    y = (m * (zf * jax.nn.sigmoid(zf))).astype(u.dtype)
    out = (y, last, steps.swapaxes(0, 1)) if keep_steps else (y, last)
    return out + (m.astype(u.dtype),) if ungated else out


def _scan_kernel(start_ref, b_ref, c_ref, u_ref, dt_ref, z_ref, a_ref, d_ref, bias_ref, h0_ref,
                 y_ref, hout_ref, *rest, N: int, Tc: int, unroll: int):
    m_ref, h_scr = rest if len(rest) == 2 else (None, rest[0])  # ``ungated``: a third output in front of the scratch
    r, k = pl.program_id(0), pl.program_id(2)
    f32 = jnp.float32

    @pl.when(k == 0)
    def _():
        h_scr[...] = h0_ref[0]

    first = start_ref[r] - k * Tc  # positions of this chunk in front of it are pad

    @pl.when(first >= Tc)
    def _():  # a chunk of pads: the state passes it; what it writes is read by nobody, but is finite
        y_ref[...] = jnp.zeros_like(y_ref)
        if m_ref is not None:
            m_ref[...] = jnp.zeros_like(m_ref)

    @pl.when(first < Tc)
    def _():
        d, bias = d_ref[...], bias_ref[...]

        def step(t, hs):
            u = u_ref[0, t].astype(f32)  # [8, 128]: 1024 channels
            dt = jnp.where(t >= first, _softplus(dt_ref[0, t].astype(f32) + bias), 0.0)
            dtu = dt * u
            y = d * u
            new = []
            for n in range(N):
                h = jnp.exp(dt * a_ref[n]) * hs[n] + dtu * b_ref[t * N + n]
                y = y + h * c_ref[t * N + n]
                new.append(h)
            if m_ref is not None:
                m_ref[0, t] = y.astype(m_ref.dtype)
            zt = z_ref[0, t].astype(f32)
            y_ref[0, t] = (y * zt * (1.0 / (1.0 + jnp.exp(-zt)))).astype(y_ref.dtype)
            return tuple(new)

        def steps(i, hs):  # ``unroll`` positions a trip, written out: Mosaic's loops unroll by 1 or whole
            for j in range(unroll):
                hs = step(i * unroll + j, hs)
            return hs

        hs = jax.lax.fori_loop(0, Tc // unroll, steps, tuple(h_scr[n] for n in range(N)))
        for n in range(N):
            h_scr[n] = hs[n]

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        hout_ref[0] = h_scr[...]


def time_chunk(S: int) -> Optional[int]:
    """Positions a grid step of the kernel walks, or None where ``S`` is not
    a whole number of chunks of at least 8."""
    for tc in (TIME_CHUNK, 128, 64, 32, 16, 8):
        if S % tc == 0:
            return tc
    return None


@functools.partial(jax.jit, static_argnames=("interpret", "ungated"))
def selective_scan_pallas(u, delta, z, A, B, C, D, dt_bias, h0, start, *, interpret: bool = False,
                          ungated: bool = False):
    """The rule above by the kernel; shapes as ``selective_scan_xla``. ``S``
    is a whole number of ``time_chunk(S)``; ``d_inner`` is padded to a whole
    number of 1024-channel tiles here (a padded channel has ``A = 0``, ``u =
    0``: its state stays what it was handed, zero)."""
    R, S, Di = u.shape
    N = A.shape[0]
    Tc = time_chunk(S)
    assert Tc is not None, f"S={S} is not a whole number of time chunks"
    pad = -Di % TILE
    rows = (Di + pad) // LANES  # sublane rows of 128 channels
    f32 = jnp.float32

    def tiled(a):  # [..., Di] -> [..., rows, 128]
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)]) if pad else a
        return a.reshape(a.shape[:-1] + (rows, LANES))

    nk = S // Tc
    seq = pl.BlockSpec((1, Tc, SUBLANES, LANES), lambda r, j, k, *_: (r, k, j, 0))
    per_channel = pl.BlockSpec((SUBLANES, LANES), lambda r, j, k, *_: (j, 0))
    state = pl.BlockSpec((1, N, SUBLANES, LANES), lambda r, j, k, *_: (r, 0, j, 0))
    scalars = pl.BlockSpec((Tc * N,), lambda r, j, k, *_: (r * nk + k,), memory_space=pltpu.SMEM)
    y, h_last, *m = pl.pallas_call(
        functools.partial(_scan_kernel, N=N, Tc=Tc, unroll=UNROLL),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R, rows // SUBLANES, nk),
            in_specs=[scalars, scalars, seq, seq, seq,
                      pl.BlockSpec((N, SUBLANES, LANES), lambda r, j, k, *_: (0, j, 0)),
                      per_channel, per_channel, state],
            out_specs=[seq, state] + [seq] * ungated,
            scratch_shapes=[pltpu.VMEM((N, SUBLANES, LANES), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((R, S, rows, LANES), u.dtype),
                   jax.ShapeDtypeStruct((R, N, rows, LANES), f32)]
        + [jax.ShapeDtypeStruct((R, S, rows, LANES), u.dtype)] * ungated,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL,
    )(start.astype(jnp.int32), B.astype(f32).reshape(-1), C.astype(f32).reshape(-1),
      tiled(u), tiled(delta), tiled(z), tiled(A.astype(f32)), tiled(D.astype(f32)), tiled(dt_bias.astype(f32)),
      tiled(h0.astype(f32)))
    y, *m = (a.reshape(R, S, Di + pad)[..., :Di] for a in [y] + m)
    return (y, h_last.reshape(R, N, Di + pad)[..., :Di], *m)


def scan_form(S: int, impl: str) -> str:
    """``KERNEL`` where a call of ``S`` positions goes through the kernel,
    else ``"selective_scan_xla"``: ``impl`` is a resolved ``attn_impl``."""
    return KERNEL if impl != "xla" and S >= 128 and time_chunk(S) is not None else "selective_scan_xla"


def selective_scan(u, delta, z, A, B, C, D, dt_bias, h0, start, *, impl: str, ungated: bool = False):
    """``(y, last state)``, and ``m`` under ``ungated``, by the form ``scan_form`` names."""
    if scan_form(u.shape[1], impl) == KERNEL:
        return selective_scan_pallas(u, delta, z, A, B, C, D, dt_bias, h0, start,
                                     interpret=impl == "pallas_interpret", ungated=ungated)
    return selective_scan_xla(u, delta, z, A, B, C, D, dt_bias, h0, start, ungated=ungated)


def causal_conv(x, history, weight, bias, activate: bool = True):
    """Depthwise causal convolution then ``silu`` (``activate``): ``x [R, S, Di]`` (zeros at
    pad positions), ``history [R, K - 1, Di]`` the inputs in front of it
    (oldest first), ``weight [K, Di]``, ``bias [Di]`` or None. Returns the activated
    ``[R, S, Di]`` in ``x``'s type and ``[R, K - 1 + S, Di]``, the history
    and the inputs in one run: the history after ``m`` of these positions is
    its rows ``[m, m + K - 1)``."""
    K, S = weight.shape[0], x.shape[1]
    run = jnp.concatenate([history.astype(x.dtype), x], axis=1)
    w = weight.astype(jnp.float32)
    acc = 0.0 if bias is None else bias.astype(jnp.float32)
    for j in range(K):  # out_t = b + sum_j w[j] * in_{t - (K - 1) + j}
        acc = acc + w[j] * jax.lax.slice_in_dim(run, j, j + S, axis=1).astype(jnp.float32)
    return (acc * jax.nn.sigmoid(acc) if activate else acc).astype(x.dtype), run
