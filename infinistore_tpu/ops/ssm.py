"""Mamba-2 (state-space duality) operations: the causal depthwise
convolution, the one-token state update of decode and the chunked scan
of prefill, in plain `jax.numpy` / einsum form. Each runs under a
`jax.named_scope` of its own (`ssm.conv`, `ssm.step`, `ssm.scan`), so a
device trace finds its operations whatever the compiler calls its
fusions; a Pallas kernel that replaces one keeps the name.

A decode step hands `conv_step` and `step` the POOLS of a layer, a row
a slot, and `decoding`'s triple: the state of the slots that decode is
advanced where it lies and every other row stays bit for bit what it
was. On a TPU `step` is then one Pallas call whose grid walks the
decoding slots' blocks of `h` alone (2.1 MB a slot a layer at
granite-4.0-h-micro's widths, of a pool of 33.5 MB).

Shapes (one group of B and C, as granite-4.0-h has it):
  x  [b, s, H, P]   heads x head width       dt [b, s, H]  (after softplus)
  B, C [b, s, N]    state width              A  [H]        (negative)
  h  [b, H, P, N]   the recurrent state (computed in float32, kept
                    in the dtype it arrives in)

The recurrence, per head: h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,
y_t = h_t C_t. A position whose dt is 0 leaves the state as it was
(decay 1, input 0): that is how padded prompt positions are masked.
The `D x_t` skip, the gate and the norm are the mixer's
(models/hybrid.py).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
# Heads a block of the decode step's kernel: a grid step moves
# _HEAD_TILE x P x N x 4 B of one slot's h in and out (1 MB at 32 x 64 x
# 128). Timed on a v5e by tools/time_state_step.py (PERF.md, PR 52).
_HEAD_TILE = 32


def decoding(valid):
    """What `conv_step` and `step` take as `rows`, from a decode step's
    `valid` [b] (the slots that hold a sequence): (valid, the slots in
    a stable order with the valid ones first [b] int32, how many they
    are [1] int32). Computed once a step, read by every state layer;
    the order is the one a learned selection's stages run in
    (`sparse_select.active_first`)."""
    order = jnp.argsort(~valid, stable=True).astype(jnp.int32)
    return valid, order, jnp.sum(valid, dtype=jnp.int32).reshape(1)


def conv_step(conv_state, xbc, w, bias, rows=None):
    """One token through the causal depthwise convolution.

    conv_state: [b, K-1, C], the last K-1 inputs; xbc: [b, C]; w: [K,
    C] (w[K-1] multiplies the current input); bias: [C]. `rows`:
    `decoding`'s triple; the tail of a row that does not decode stays
    as it is (the pool is small, 0.84 MB a layer of 16 slots: it is
    passed whole, in one fusion).
    Returns (silu(conv) [b, C] in xbc's dtype, new conv_state)."""
    with jax.named_scope("ssm.conv"):
        window = jnp.concatenate(
            [conv_state, xbc[:, None].astype(conv_state.dtype)], axis=1)
        out = jnp.einsum("bkc,kc->bc", window.astype(F32), w.astype(F32))
        out = jax.nn.silu(out + bias.astype(F32)).astype(xbc.dtype)
        new = window[:, 1:]
        if rows is not None:
            new = jnp.where(rows[0][:, None, None], new, conv_state)
        return out, new


def conv_seq(conv_state, xbc, w, bias):
    """A sequence through the same convolution, starting from the tail
    `conv_state` [b, K-1, C] of what came before (zeros at position 0).

    xbc: [b, s, C]. Returns (silu(conv) [b, s, C], `full` [b, K-1 + s,
    C]: the inputs with the incoming tail in front, of which rows
    [p, p + K-1) are the tail after p tokens: `conv_tail`)."""
    k = w.shape[0]
    s = xbc.shape[1]
    with jax.named_scope("ssm.conv"):
        full = jnp.concatenate(
            [conv_state, xbc.astype(conv_state.dtype)], axis=1)
        out = bias.astype(F32)
        for j in range(k):
            out = out + full[:, j:j + s].astype(F32) * w[j].astype(F32)
        return jax.nn.silu(out).astype(xbc.dtype), full


def conv_tail(full, pos, k):
    """The convolution's state after `pos` tokens of `conv_seq`'s
    `full` (pos: traced scalar): rows [pos, pos + k - 1)."""
    return jax.lax.dynamic_slice_in_dim(full, pos, k - 1, axis=1)


def head_tile(H):
    """Heads a block of `step_kernel` over H heads: what H and
    _HEAD_TILE both divide by, so that every head lies in exactly one
    block whatever H is (48 heads: 16), where that is a multiple of 8
    (the heads are a block's second-to-last dimension in `xdt` and `y`,
    which the chip tiles by 8), else all H (a block that spans a
    dimension is always allowed)."""
    tile = math.gcd(H, _HEAD_TILE)
    return tile if tile % 8 == 0 else H


def _step_body(order_ref, h_ref, decay_ref, xdt_ref, b_ref, c_ref, h_out,
               y_out):
    """One block of heads of one slot: h [1, T, P, N]; decay [1, T, 1,
    N] (a head's, along the lanes); xdt [1, T, P]; B, C [1, 1, N]."""
    new = h_ref[0].astype(F32) * decay_ref[0] + (
        xdt_ref[0][:, :, None] * b_ref[0][None])
    h_out[0] = new.astype(h_out.dtype)
    y_out[0] = jnp.sum(new * c_ref[0][None], axis=-1)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def step_kernel(h, decay, xdt, B, C, order, count, tile=None,
                interpret=False):
    """`step`'s update of the first `count` [1] slots of `order` [b], as
    ONE Pallas call over the pool `h` [b, H, P, N], which comes back
    ALIASED: a grid of (count, H / tile) steps, step (i, j) holding
    heads [j tile, (j + 1) tile) of slot order[i]. The grid's first
    bound is the count itself, so no step runs for a slot that does
    not decode, and the order is scalar-prefetched, so the index maps
    read it. decay: [b, H]; xdt: [b, H, P]; B, C: [b, N], float32.
    `tile` is the tests' (interpret mode, small H): the program takes
    `head_tile(H)`, and a tile that does not divide H is refused, for
    the heads past the last whole tile would never be advanced.
    Returns (y [b, H, P] float32, of which the rows of the slots that
    were not run are NOT WRITTEN; h)."""
    b, H, P, N = h.shape
    tile = tile or head_tile(H)
    if H % tile:
        raise ValueError(f"a tile of {tile} heads does not divide {H}")

    def heads(*shape):
        """Block [1, tile, *shape] of the slot and head tile of a step."""
        return pl.BlockSpec(
            (1, tile, *shape),
            lambda i, j, order: (order[i], j, *(0,) * len(shape)))

    slot = pl.BlockSpec((1, 1, N), lambda i, j, order: (order[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # order
        grid=(count[0], H // tile),
        in_specs=[heads(P, N), heads(1, N), heads(P), slot, slot],
        out_specs=[heads(P, N), heads(P)],
    )
    h, y = pl.pallas_call(
        _step_body,
        out_shape=[jax.ShapeDtypeStruct(h.shape, h.dtype),
                   jax.ShapeDtypeStruct((b, H, P), F32)],
        grid_spec=grid_spec,
        input_output_aliases={1: 0},  # the pool
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, 12 * tile * P * N * 4)),
        interpret=interpret,
    )(order, h, jnp.broadcast_to(decay[:, :, None, None], (b, H, 1, N)),
      xdt, B[:, None], C[:, None])
    return y, h


def _step_whole(h, decay, xdt, B, C):
    """`step` over every row, in XLA."""
    new = h.astype(F32) * decay[..., None, None] + (
        xdt[..., None] * B[:, None, None, :])
    return jnp.einsum("bhpn,bn->bhp", new, C), new.astype(h.dtype)


def step(h, x, dt, A, B, C, rows=None):
    """One token of the recurrence for every row of the batch, or with
    `rows` (`decoding`'s triple) for the rows that decode: the others'
    state stays bit for bit what it was and their y is 0. On a TPU
    that is `step_kernel`, which moves the decoding rows alone;
    elsewhere every row is computed and the decoding ones are kept.

    h: [b, H, P, N] float32; x: [b, H, P]; dt: [b, H] float32; A: [H];
    B, C: [b, N]. Returns (y [b, H, P] float32, new h)."""
    with jax.named_scope("ssm.step"):
        decay = jnp.exp(dt * A)                              # [b, H]
        xdt = x.astype(F32) * dt[..., None]                  # [b, H, P]
        B, C = B.astype(F32), C.astype(F32)
        if rows is None:
            return _step_whole(h, decay, xdt, B, C)
        run = rows[0]
        if jax.default_backend() == "tpu":
            y, new = step_kernel(h, decay, xdt, B, C, *rows[1:])
        else:
            y, new = _step_whole(h, decay, xdt, B, C)
            new = jnp.where(run[:, None, None, None], new, h)
        return jnp.where(run[:, None, None], y, 0.0), new


def scan(h0, x, dt, A, B, C, chunk):
    """The recurrence over a sequence in chunks of `chunk` positions
    (the state-space-duality form): inside a chunk a masked
    quadratic product, between chunks the state carried by a scan over
    the chunks. s need not be a multiple of `chunk`: the sequence is
    padded with dt = 0, which leaves state and outputs as they are.

    h0: [b, H, P, N] float32; x: [b, s, H, P]; dt: [b, s, H] float32
    (0 at masked positions); A: [H]; B, C: [b, s, N].
    Returns (y [b, s, H, P] float32, h after the last position)."""
    b, s, H, P = x.shape
    q = min(chunk, s)
    pad = -s % q
    c = (s + pad) // q
    with jax.named_scope("ssm.scan"):
        def chunks(a):
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            return a.reshape(b, c, q, *a.shape[2:])

        dt_c = chunks(dt)                                    # [b,c,q,H]
        xdt = chunks(x.astype(F32) * dt[..., None]).astype(x.dtype)
        Bc, Cc = chunks(B), chunks(C)                        # [b,c,q,N]
        a_cum = jnp.cumsum(dt_c * A, axis=2)                 # [b,c,q,H]
        # Inside a chunk: y_l += sum_{m<=l} (C_l . B_m) exp(a_l - a_m)
        # (x dt)_m.
        cb = jnp.einsum("bcln,bcmn->bclm", Cc, Bc,
                        preferred_element_type=F32)
        diff = a_cum[:, :, :, None, :] - a_cum[:, :, None, :, :]
        tril = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
        decay = jnp.exp(jnp.where(tril, diff, -jnp.inf))     # [b,c,l,m,H]
        w = (cb[..., None] * decay).astype(x.dtype)
        y = jnp.einsum("bclmh,bcmhp->bclhp", w, xdt,
                       preferred_element_type=F32)
        # What each chunk adds to the state at its own end.
        to_end = jnp.exp(a_cum[:, :, -1:, :] - a_cum)        # [b,c,q,H]
        add = jnp.einsum("bcmn,bcmhp->bchpn", Bc.astype(F32),
                         xdt.astype(F32) * to_end[..., None])
        total = jnp.exp(a_cum[:, :, -1, :])                  # [b,c,H]

        def carry(h, inp):
            add_c, total_c = inp
            return h * total_c[..., None, None] + add_c, h

        h_end, h_in = jax.lax.scan(
            carry, h0.astype(F32),
            (jnp.moveaxis(add, 1, 0), jnp.moveaxis(total, 1, 0)))
        h_in = jnp.moveaxis(h_in, 0, 1)                      # [b,c,H,P,N]
        # What the state a chunk starts from adds to its outputs.
        y = y + jnp.einsum("bcln,bchpn->bclhp", Cc.astype(F32),
                           h_in) * jnp.exp(a_cum)[..., None]
        return y.reshape(b, c * q, H, P)[:, :s], h_end.astype(h0.dtype)
