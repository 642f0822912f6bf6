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

A second recurrence, Mamba-1's SELECTIVE scan (models/phi_flash.py),
whose decay is per channel AND per state element, so the chunked
matrix form above (a scalar decay a head) does not compute it:
  x, dt [b, s, C] (dt after softplus)       B, C [b, s, N]
  A [N, C] (negative)                       h [b, N, C]
  h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
  y_t[c] = sum_n h_t[n, c] C_t[n]
The state lies with its CHANNELS along the lanes (5,120 of them at
Phi-4-mini-flash's widths) and its 16 elements along the sublanes:
[C, N] would pad 16 lanes to 128. `selective_scan` (an admission) is
sequential in time, the state in fast memory; `selective_step` (a
decode step) moves the decoding slots' rows alone, as `step` does. On
a TPU both are Pallas calls, elsewhere plain `jax.numpy`.
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


# ---- the selective recurrence (Mamba-1) --------------------------------

# Time steps a grid step of the scan's kernel holds (x, dt and y blocks
# of _SCAN_CHUNK x C float32 each, B and C as columns) and channels a
# tile of its inner loop (the tile's state and its A stay in registers
# over the chunk: 16 x 512 float32 are 8 vregs each).
_SCAN_CHUNK = 64
_CHANNEL_TILE = 512


def _selective_whole(h, x, dt, A, B, C):
    """One token of the selective recurrence for every row, in XLA.
    h: [b, N, C]; x, dt: [b, C]; A: [N, C]; B, C: [b, N]; float32."""
    new = jnp.exp(dt[:, None, :] * A) * h.astype(F32) \
        + (dt * x)[:, None, :] * B[:, :, None]
    return jnp.sum(new * C[:, :, None], axis=1), new.astype(h.dtype)


def _selective_step_body(order_ref, h_ref, a_ref, dt_ref, x_ref, b_ref,
                         c_ref, h_out, y_out):
    """One slot: h [1, N, C]; A [N, C]; dt, x [1, 1, C]; B, C [1, N, 1]
    (columns: an element a sublane, broadcast along the lanes)."""
    dt = dt_ref[0]
    new = jnp.exp(dt * a_ref[...]) * h_ref[0].astype(F32) \
        + (dt * x_ref[0]) * b_ref[0]
    h_out[0] = new.astype(h_out.dtype)
    y_out[0] = jnp.sum(new * c_ref[0], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_step_kernel(h, x, dt, A, B, C, order, count, interpret=False):
    """`selective_step`'s update of the first `count` [1] slots of
    `order` [b], as ONE Pallas call over the pool `h` [b, N, C], which
    comes back ALIASED: a grid of `count` steps, step i holding slot
    order[i] whole (328 KB at 16 x 5,120). Returns (y [b, C] float32,
    of which the rows of the slots not run are NOT WRITTEN; h)."""
    b, N, Cw = h.shape

    def slot(*shape):
        return pl.BlockSpec((1, *shape),
                            lambda i, order: (order[i], *(0,) * len(shape)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # order
        grid=(count[0],),
        in_specs=[slot(N, Cw), pl.BlockSpec((N, Cw), lambda i, order: (0, 0)),
                  slot(1, Cw), slot(1, Cw), slot(N, 1), slot(N, 1)],
        out_specs=[slot(N, Cw), slot(1, Cw)],
    )
    h, y = pl.pallas_call(
        _selective_step_body,
        out_shape=[jax.ShapeDtypeStruct(h.shape, h.dtype),
                   jax.ShapeDtypeStruct((b, 1, Cw), F32)],
        grid_spec=grid_spec,
        input_output_aliases={1: 0},  # the pool
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(order, h, A, dt[:, None], x[:, None], B[:, :, None], C[:, :, None])
    return y[:, 0], h


def selective_step(h, x, dt, A, B, C, rows=None):
    """One token of the selective recurrence for every row of the
    batch, or with `rows` (`decoding`'s triple) for the rows that
    decode: the others' state stays bit for bit what it was and their
    y is 0. On a TPU that is `selective_step_kernel`.

    h: [b, N, C] float32; x: [b, C]; dt: [b, C] float32; A: [N, C];
    B, C: [b, N]. Returns (y [b, C] float32, new h)."""
    with jax.named_scope("ssm.step"):
        x, B, C = x.astype(F32), B.astype(F32), C.astype(F32)
        if rows is None:
            return _selective_whole(h, x, dt, A, B, C)
        run = rows[0]
        if jax.default_backend() == "tpu":
            y, new = selective_step_kernel(h, x, dt, A, B, C, *rows[1:])
        else:
            y, new = _selective_whole(h, x, dt, A, B, C)
            new = jnp.where(run[:, None, None], new, h)
        return jnp.where(run[:, None], y, 0.0), new


def _selective_scan_body(h0_ref, a_ref, dt_ref, x_ref, b_ref, c_ref, y_ref,
                         h_ref, *, tile):
    """One chunk of one sequence: h0, h [1, N, C] (h is the carry: its
    block stays in fast memory over the chunks); A [N, C]; dt, x, y [1,
    T, C]; B, C [1, T, N, 1]. A tile of channels at a time, the tile's
    state in registers over the chunk's T steps, 8 steps (one tile of
    sublanes of dt, x and y) a turn of the loop."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = h0_ref[...]

    T, Cw = dt_ref.shape[1:]
    for at in range(0, Cw, tile):
        lanes = slice(at, at + tile)
        a = a_ref[:, lanes]

        def eight(g, h):
            r = pl.ds(pl.multiple_of(g * 8, 8), 8)
            dt8, x8 = dt_ref[0, r, lanes], x_ref[0, r, lanes]
            ys = []
            for j in range(8):
                dt = dt8[j:j + 1]
                h = jnp.exp(dt * a) * h \
                    + (dt * x8[j:j + 1]) * b_ref[0, g * 8 + j]
                ys.append(jnp.sum(h * c_ref[0, g * 8 + j], axis=0,
                                  keepdims=True))
            y_ref[0, r, lanes] = jnp.concatenate(ys, axis=0)
            return h

        h_ref[0, :, lanes] = jax.lax.fori_loop(
            0, T // 8, eight, h_ref[0, :, lanes].astype(F32)
        ).astype(h_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "tile", "interpret"))
def selective_scan_kernel(h0, x, dt, A, B, C, chunk=_SCAN_CHUNK, tile=None,
                          interpret=False):
    """`selective_scan` as ONE Pallas call: a grid of (sequences, chunks
    of `chunk` steps), the state carried from chunk to chunk in its
    output block. s must be a multiple of `chunk`, `chunk` of 8, and the
    channels of `tile`. Returns (y [b, s, C] float32, h [b, N, C])."""
    b, s, Cw = x.shape
    N = A.shape[0]
    tile = tile or math.gcd(Cw, _CHANNEL_TILE)
    if s % chunk or chunk % 8 or Cw % tile:
        raise ValueError(f"{s} steps in chunks of {chunk}, {Cw} channels "
                         f"in tiles of {tile}")
    state = pl.BlockSpec((1, N, Cw), lambda i, j: (i, 0, 0))
    steps = pl.BlockSpec((1, chunk, Cw), lambda i, j: (i, j, 0))
    cols = pl.BlockSpec((1, chunk, N, 1), lambda i, j: (i, j, 0, 0))
    y, h = pl.pallas_call(
        functools.partial(_selective_scan_body, tile=tile),
        out_shape=[jax.ShapeDtypeStruct((b, s, Cw), F32),
                   jax.ShapeDtypeStruct(h0.shape, h0.dtype)],
        grid=(b, s // chunk),
        in_specs=[state, pl.BlockSpec((N, Cw), lambda i, j: (0, 0)),
                  steps, steps, cols, cols],
        out_specs=[steps, state],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(h0, A, dt, x, B[..., None], C[..., None])
    return y, h


def selective_scan(h0, x, dt, A, B, C):
    """The selective recurrence over a sequence from the state `h0`.
    A position whose dt is 0 leaves the state as it was (decay 1, input
    0): that is how padded prompt positions are masked. On a TPU
    `selective_scan_kernel` (s padded to whole chunks with dt = 0),
    elsewhere a `lax.scan` over the positions.

    h0: [b, N, C] float32; x: [b, s, C]; dt: [b, s, C] float32; A: [N,
    C]; B, C: [b, s, N]. Returns (y [b, s, C] float32, h after the last
    position)."""
    s = x.shape[1]
    with jax.named_scope("ssm.scan"):
        x, B, C = x.astype(F32), B.astype(F32), C.astype(F32)
        if jax.default_backend() == "tpu":
            chunk = min(_SCAN_CHUNK, -(-s // 8) * 8)
            pad = -s % chunk

            def steps(a):
                return jnp.pad(a, ((0, 0), (0, pad))
                               + ((0, 0),) * (a.ndim - 2))

            y, h = selective_scan_kernel(h0, steps(x), steps(dt), A,
                                         steps(B), steps(C), chunk=chunk)
            return y[:, :s], h

        def one(h, inp):
            y, h = _selective_whole(h, *inp[:2], A, *inp[2:])
            return h, y

        h, y = jax.lax.scan(one, h0, tuple(
            jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
        return jnp.moveaxis(y, 0, 1), h
