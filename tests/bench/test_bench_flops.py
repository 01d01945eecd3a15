"""``bench/flops`` against XLA's count of the program's compiled server
step, on the CPU at small sizes.

XLA's ``cost_analysis()`` counts every floating-point operation of the
compiled step: the matmuls that ``bench/flops`` counts, and besides them
the elementwise work (norms, softmax, activations, residual adds, AdamW's
update) that model FLOPs leave out.  So XLA's count is at least the model
count, and above it by the elementwise share: 4.5-5.2% at these widths
(d_model 128, 32 tokens), and less at published widths, where a matmul
does more operations for each element it writes."""
import json

import jax
import jax.numpy as jnp
import pytest

from bench.flops import dense, encoder
from bench_helpers import ROOT

SIZES = {"hidden_size": 128, "num_hidden_layers": 4, "intermediate_size": 512,
         "vocab_size": 512, "max_position_embeddings": 64}
# XLA's count over the model count: elementwise work, about 5% here
ELEMENTWISE_MARGIN = 0.1
# a dense decoder of the program's granite-3-2b family at its published
# widths (causal, gated MLP, grouped KV heads), for ``bench/flops/dense``
DECODER = {"program": {"registry": "granite-3-2b"}, "hidden_size": 2048,
           "num_hidden_layers": 40, "num_attention_heads": 32,
           "num_key_value_heads": 8, "head_dim": 64, "intermediate_size": 8192,
           "vocab_size": 49155, "max_position_embeddings": 4096,
           "causal": True, "gated_mlp": True, "num_labels": 6,
           "torch_dtype": "bfloat16",
           "lora": {"rank": 16, "alpha": 32.0, "targets": ["wq", "wk", "wv", "wo"]}}


def _config(name):
    if name == "dense-decoder":
        return json.loads(json.dumps(DECODER))
    return json.loads((ROOT / f"bench/configs/{name}.json").read_text())


def _small(name, heads, kv):
    c = _config(name)
    c.update(SIZES, num_attention_heads=heads, num_key_value_heads=kv,
             head_dim=SIZES["hidden_size"] // heads)
    c["lora"] = dict(c["lora"], rank=8)
    return c


def _program_cfg(c):
    from repro.configs import REGISTRY
    from repro.configs.base import LoRAConfig
    return REGISTRY[c["program"]["registry"]].with_(
        d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], max_position=c["max_position_embeddings"],
        n_classes=c["num_labels"], dtype="float32",
        lora=LoRAConfig(rank=c["lora"]["rank"], alpha=c["lora"]["alpha"],
                        targets=tuple(c["lora"]["targets"])))


@pytest.mark.parametrize("name,module,heads,kv", [
    ("bert-base", encoder, 4, 4),
    ("dense-decoder", dense, 8, 2),
])
@pytest.mark.parametrize("cut", [1, 2])
def test_server_step_flops_match_xla(name, module, heads, kv, cut):
    from repro.core import splitfl
    from repro.models import build_model
    from repro.optim import AdamW

    c = _small(name, heads, kv)
    c["torch_dtype"] = "float32"
    model = build_model(_program_cfg(c))
    batch, seq = 4, 32
    step = splitfl.make_server_step_cls(model, AdamW(1e-5), path="sliced",
                                        static_cut=cut)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    lora = jax.eval_shape(model.init_lora, jax.random.PRNGKey(1))
    head = params["cls_head"]
    opt = jax.eval_shape(AdamW(1e-5).init, {"lora": lora, "head": head})
    v = jax.ShapeDtypeStruct((batch, seq, c["hidden_size"]), jnp.float32)
    data = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
            "label": jax.ShapeDtypeStruct((batch,), jnp.int32)}
    compiled = step.lower(params, lora, head, opt, v, data).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    xla = float(cost["flops"])
    ours = module.step_work(c, batch, seq, first=cut, input_grad=True)["flops"]
    assert ours <= xla <= ours * (1 + ELEMENTWISE_MARGIN), (ours, xla)


def test_round_work_counts_every_client():
    c = json.loads((ROOT / "bench/configs/bert-base.json").read_text())
    t = json.loads((ROOT / "bench/traffic/paper6.json").read_text())
    w = encoder.round_work(c, t)
    assert w["tokens"] == 6 * 16 * 128
    assert w["flops"] == 6 * encoder.step_work(c, 16, 128)["flops"]
    # about 0.36 GFLOP a token for bert-base, 9.8 for the 2B decoder
    assert 3.0e8 < w["flops"] / w["tokens"] < 4.0e8
    wg = dense.round_work(_config("dense-decoder"), t)
    assert 9.0e9 < wg["flops"] / wg["tokens"] < 1.05e10
