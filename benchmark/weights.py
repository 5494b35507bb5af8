"""Seeded weights of a dense RoPE/GQA decoder, as the benchmark makes them.

Plain jax.numpy: nothing of the program is imported. A leaf is
`mean + std * normal(fold_in(key(seed), index))`, rounded to the type it is
served or trained in, so the program and the reference get the same values
from `--seed` alone and neither takes anything from the other.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LINEAR_STD = 0.02

# leaves of one decoder layer, in the order the program's model lists them
LAYER_LEAVES = ("input_norm", "wq", "wk", "wv", "wo", "post_norm",
                "w_gate", "w_up", "w_down")


def leaf_specs(cfg: dict) -> list[tuple[str, tuple, float, float]]:
    """[(name, shape, mean, std)]: embedding, each layer's nine leaves, the
    final norm, the untied head. Matrices are [in, out]."""
    h, inter, vocab = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    head_dim = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q_out = cfg["num_attention_heads"] * head_dim
    kv_out = cfg["num_key_value_heads"] * head_dim
    shapes = {"input_norm": (h,), "wq": (h, q_out), "wk": (h, kv_out),
              "wv": (h, kv_out), "wo": (q_out, h), "post_norm": (h,),
              "w_gate": (h, inter), "w_up": (h, inter), "w_down": (inter, h)}
    specs = [("embed", (vocab, h), 0.0, LINEAR_STD)]
    for i in range(cfg["num_hidden_layers"]):
        for leaf in LAYER_LEAVES:
            norm = leaf.endswith("norm")
            specs.append((f"layers.{i}.{leaf}", shapes[leaf],
                          1.0 if norm else 0.0, 0.0 if norm else LINEAR_STD))
    specs.append(("final_norm", (h,), 1.0, 0.0))
    specs.append(("head", (h, vocab), 0.0, LINEAR_STD))
    return specs


def seed_parts(seed: int) -> tuple[int, int]:
    """Any whole number up to 2**48 as two halves: a 32-bit JAX cannot take
    a seed above 2**31. Hand the halves to a jitted function as ARGUMENTS: a
    seed closed over is a constant of the program, and every new seed would
    compile it again."""
    seed = int(seed)
    return seed & 0xFFFFFF, (seed >> 24) & 0xFFFFFF


def key_of(parts):
    return jax.random.fold_in(jax.random.key(parts[0]), parts[1])


def seed_key(seed: int):
    return key_of(seed_parts(seed))


def make_leaf(key, index, shape, mean, std, dtype):
    noise = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
    return (mean + std * noise).astype(dtype)


@functools.partial(jax.jit, static_argnames=("specs", "dtype"),
                   donate_argnums=(1,))
def _make_all(key, donated, *, specs, dtype):
    del donated  # their buffers are handed to the outputs
    return [make_leaf(key, i, shape, mean, std, dtype)
            for i, (_, shape, mean, std) in enumerate(specs)]


def make_all(seed: int, specs, dtype, donate=None) -> list:
    """Every leaf in ONE jitted call on the default device. `donate` is a
    list of arrays of the same shapes and type (the program's own initial
    values) whose memory the new leaves take over."""
    return _make_all(seed_key(seed), donate or [], specs=tuple(specs),
                     dtype=jnp.dtype(dtype))
