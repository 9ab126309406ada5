"""Operations and bytes that one call of ``fused_lookup_call`` needs,
from the model's shapes and the number of keys.

What is counted is what the algorithm needs, not the padded layout the
kernel computes on:

* operations: the multiply-adds of every dense layer of the model at its
  own widths, two operations each.  The first layer takes the one-hot
  digit features, ``base * width`` wide, as the model defines it.
  Biases, ReLU and argmax are left out.
* bytes: per key, its int32 key in, one int32 code per head out, the
  32-bit existence word it reads and the int32 existence flag out; per
  call, the float32 weights read once.

The functions take the shapes as plain numbers (``mlp_layers``), so they
stay independent of the store's classes.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple


def mlp_layers(feature_dim: int, shared: Sequence[int], private: Dict[str, Sequence[int]],
               cards: Dict[str, int], tasks: Optional[Sequence[str]] = None
               ) -> List[Tuple[int, int]]:
    """``(in, out)`` of every dense layer that answers ``tasks`` (all
    heads by default): the shared trunk, then each head's private layers
    and its output layer."""
    layers, d = [], feature_dim
    for h in shared:
        layers.append((d, h))
        d = h
    trunk = d
    for task in sorted(cards if tasks is None else tasks):
        d = trunk
        for h in private[task]:
            layers.append((d, h))
            d = h
        layers.append((d, cards[task]))
    return layers


def ops_per_key(layers: Sequence[Tuple[int, int]]) -> int:
    return sum(2 * i * o for i, o in layers)


def weight_bytes(layers: Sequence[Tuple[int, int]]) -> int:
    return sum(4 * (i * o + o) for i, o in layers)


def bytes_per_key(num_heads: int) -> int:
    return 4 + 4 * num_heads + 4 + 4


def model_ops(model, dispatched: Sequence[Tuple[Sequence[str], int]]) -> int:
    """Operations of the keys handed to the model, ``(heads, keys)``
    pairs; ``model`` is ``(feature_dim, shared, private, cards)``."""
    return sum(ops_per_key(mlp_layers(*model, tasks)) * keys for tasks, keys in dispatched)


def roofline_seconds(model, dispatched: Sequence[Tuple[Sequence[str], int]], calls: int,
                     peak: dict) -> Tuple[float, str]:
    """Least time ``calls`` calls over the ``(heads, keys)`` pairs of
    ``dispatched`` could take, and which bound sets it (``"compute"`` or
    ``"memory"``).  Summed over calls before the larger bound is taken,
    and each call charged the weights of the fewest heads asked for,
    which can only lower the least time, so the share it gives is never
    too high."""
    compute = model_ops(model, dispatched) / peak["flops_bf16"]
    weights = min(weight_bytes(mlp_layers(*model, tasks)) for tasks, _ in dispatched)
    memory = (sum(bytes_per_key(len(tasks)) * keys for tasks, keys in dispatched)
              + weights * calls) / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
