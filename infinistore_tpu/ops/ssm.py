"""Mamba-2 (state-space duality) operations: the causal depthwise
convolution, the one-token state update of decode and the chunked scan
of prefill, in plain `jax.numpy` / einsum form. Each runs under a
`jax.named_scope` of its own (`ssm.conv`, `ssm.step`, `ssm.scan`), so a
device trace finds its operations whatever the compiler calls its
fusions; a Pallas kernel that replaces one keeps the name.

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

import jax
import jax.numpy as jnp

F32 = jnp.float32


def conv_step(conv_state, xbc, w, bias):
    """One token through the causal depthwise convolution.

    conv_state: [b, K-1, C], the last K-1 inputs; xbc: [b, C]; w: [K,
    C] (w[K-1] multiplies the current input); bias: [C].
    Returns (silu(conv) [b, C] in xbc's dtype, new conv_state)."""
    with jax.named_scope("ssm.conv"):
        window = jnp.concatenate(
            [conv_state, xbc[:, None].astype(conv_state.dtype)], axis=1)
        out = jnp.einsum("bkc,kc->bc", window.astype(F32), w.astype(F32))
        out = jax.nn.silu(out + bias.astype(F32)).astype(xbc.dtype)
        return out, window[:, 1:]


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


def step(h, x, dt, A, B, C):
    """One token of the recurrence for every row of the batch.

    h: [b, H, P, N] float32; x: [b, H, P]; dt: [b, H] float32; A: [H];
    B, C: [b, N]. Returns (y [b, H, P] float32, new h)."""
    with jax.named_scope("ssm.step"):
        decay = jnp.exp(dt * A)                              # [b, H]
        xdt = x.astype(F32) * dt[..., None]                  # [b, H, P]
        new = h.astype(F32) * decay[..., None, None] + (
            xdt[..., None] * B.astype(F32)[:, None, None, :])
        y = jnp.einsum("bhpn,bn->bhp", new, C.astype(F32))
        return y, new.astype(h.dtype)


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
