"""Work of one round of split-federated LoRA training on a dense
transformer (pre-LN attention + MLP blocks, classifier on the first token).

Model FLOPs count what the training needs, once:

- every layer's forward, the client prefix and the server suffix alike,
  attention's score and value matmuls included (a causal mask halves the
  pairs, counted as S(S+1)/2 per sequence);
- the activation gradients the adapters need, from the loss down to layer
  0 (layer 0's gradient with respect to its own input is not needed, so
  its q/k/v projections have none);
- the adapter and classifier weight gradients.

They leave out frozen-weight gradients (none are computed), recomputation
(the client backward runs its forward again), padded layers, norms,
softmax and other elementwise work.

The least bytes count each frozen weight read once a client step, which no
schedule can avoid; with 2,048 tokens a step the FLOPs bound is the larger
in both configurations, by about tenfold.
"""
from __future__ import annotations


def _dtype_bytes(name: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2}[name]


def step_work(c: dict, batch: int, seq: int, first: int = 0,
              input_grad: bool = False) -> dict:
    """FLOPs and least bytes of layers [first, L) and the classifier,
    forward and backward, on ``batch`` sequences of ``seq`` tokens.

    ``first=0`` is a client's whole step, which needs no gradient with
    respect to the embedding; the server step from a cut also returns the
    gradient of its input activations (``input_grad``)."""
    d, n_layers = c["hidden_size"], c["num_hidden_layers"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    ff, r = c["intermediate_size"], c["lora"]["rank"]
    adapted = set(c["lora"]["targets"])
    projs = {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
             "wo": (h * hd, d), "wu": (d, ff), "wd": (ff, d)}
    if c["gated_mlp"]:
        projs["wg"] = (d, ff)
    tokens = batch * seq
    pairs = batch * (seq * (seq + 1) // 2 if c["causal"] else seq * seq)
    wbytes = _dtype_bytes(c["torch_dtype"])

    flops = nbytes = 0
    for layer in range(first, n_layers):
        for name, (fan_in, fan_out) in projs.items():
            needs_dx = input_grad or not (layer == first
                                          and name in ("wq", "wk", "wv"))
            mm = 2 * tokens * fan_in * fan_out
            flops += mm + (mm if needs_dx else 0)
            nbytes += fan_in * fan_out * wbytes
            if name in adapted:
                lo_in, lo_out = 2 * tokens * r * fan_in, 2 * tokens * r * fan_out
                flops += lo_in + lo_out                  # forward: x·Aᵀ, ·Bᵀ
                flops += 2 * lo_out + lo_in              # dlo, dB, dA
                flops += lo_in if needs_dx else 0        # dx through A
        attn = 2 * h * hd * pairs                        # one of QKᵀ or PV
        flops += 2 * attn + 4 * attn                     # forward, backward
    flops += 3 * 2 * batch * d * c["num_labels"]         # logits, dW, dx
    return {"flops": flops, "bytes": nbytes}


def round_work(c: dict, traffic: dict) -> dict:
    """Work of one round: every client of the fleet takes one step."""
    step = step_work(c, traffic["batch"], traffic["seq_len"])
    n = len(traffic["cuts"])
    return {"flops": n * step["flops"], "bytes": n * step["bytes"],
            "tokens": n * traffic["batch"] * traffic["seq_len"]}
