"""SmallThinker-style sparse decoder: full and banded attention layers
in one model, many small experts, the router before attention. The
fourth family on the one decoder stack (models/decoder.py).

What is its own:

- the per-layer spec (LlamaConfig's): `layer_bands[i]` is layer i's
  band (0: full causal attention, w: the last w positions) and
  `layer_rope[i]` says whether it rotates; the published model is full
  attention WITHOUT rotary every fourth layer and a 4,096-position
  band WITH rotary on the others. Two kinds of attention layer mean
  two kinds of page (`cfg.two_kinds`): the serving engine keeps the
  full layers' pages for the life of a sequence and the banded layers'
  for the band (serving.py).
- the feed-forward block: `top_k` of `n_experts` ReGLU experts a token
  (relu on the gate branch), gates = softmax over the chosen logits,
  through models/moe.py's sorted dispatch (no capacity, nothing
  dropped); the router reads the normalised input of the layer's
  ATTENTION block (`early_router`), not the feed-forward's own.

Parameters are models/moe.py's (router float32, experts stacked on a
leading axis), so the page contract, the loops and the engine's
programs are shared with every family.
"""

from dataclasses import dataclass

from . import decoder, moe


@dataclass(frozen=True)
class SmallThinkerConfig(moe.MoEConfig):
    """MoEConfig (whose `layer_bands` and `layer_rope` are the
    per-layer spec) plus where the router sits; `act` is the gate
    branch's activation."""

    early_router: bool = True  # the router reads the attention block's
    #                            normalised input
    act: str = "relu"


init_params = moe.init_params


def _block(layer, x, cfg, valid, h_attn=None):
    return moe.sorted_moe_mlp(layer, x, cfg, valid, h_attn,
                              early_router=cfg.early_router)


_forward_stack, decode_step, verify_step = decoder.bind(_block)


def prefill(params, cfg: SmallThinkerConfig, tokens, keep=None):
    logits, kvs, _ = _forward_stack(params, cfg, tokens, keep=keep)
    return logits, kvs


forward_dense = prefill


def prefill_with_prefix(params, cfg: SmallThinkerConfig, tokens,
                        prefix_kvs, pos0=0, keep=None):
    """Suffix prefill over a cached prefix; each layer's prefix is what
    that layer may attend (decoder.forward_stack: a banded layer's may
    be the tail its band needs)."""
    logits, kvs, _ = _forward_stack(params, cfg, tokens, prefix_kvs,
                                    pos0=pos0, keep=keep)
    return logits, kvs
