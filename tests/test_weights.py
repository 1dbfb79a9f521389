"""Weight loader: HF safetensors checkpoints → our layouts, logit parity."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llms_on_kubernetes_tpu.configs import from_hf_config
from llms_on_kubernetes_tpu.engine.cache import CacheConfig, PageAllocator, init_pages
from llms_on_kubernetes_tpu.engine.weights import load_hf_params, resolve_model_dir
from llms_on_kubernetes_tpu.models.decoder import forward_prefill


def _prefill_logits(cfg, params, prompt):
    cc = CacheConfig(num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
                     head_dim=cfg.head_dim, num_pages=32, page_size=4,
                     pages_per_slot=8, dtype="float32")
    kp, vp = init_pages(cc)
    al = PageAllocator(cc.num_pages, cc.page_size, 1, cc.pages_per_slot)
    al.allocate(0, len(prompt))
    logits, _, _ = forward_prefill(
        params, cfg, jnp.asarray([prompt], jnp.int32),
        jnp.asarray([len(prompt)], jnp.int32), kp, vp,
        jnp.asarray(al.page_tables),
    )
    return np.asarray(logits)[0]


@pytest.mark.parametrize("family",
                         ["llama", "qwen2", "mixtral", "qwen3_moe", "phi3"])
def test_load_hf_checkpoint_logit_parity(tmp_path, family):
    torch = pytest.importorskip("torch")
    import transformers

    common = dict(
        vocab_size=96, hidden_size=32, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    if family == "llama":
        hf_cfg = transformers.LlamaConfig(attention_bias=False, **common)
        hf = transformers.LlamaForCausalLM(hf_cfg)
    elif family == "qwen2":
        hf_cfg = transformers.Qwen2Config(**common)
        hf = transformers.Qwen2ForCausalLM(hf_cfg)
    elif family == "mixtral":
        hf_cfg = transformers.MixtralConfig(
            num_local_experts=4, num_experts_per_tok=2, **common
        )
        hf = transformers.MixtralForCausalLM(hf_cfg)
    elif family == "phi3":
        # phi3: FUSED qkv_proj / gate_up_proj tensors — the KeyError
        # fallback split path in hf_layer_maps, otherwise untested vs HF
        # default pad_token_id (32000) would index past the tiny vocab
        hf_cfg = transformers.Phi3Config(pad_token_id=0, **common)
        hf = transformers.Phi3ForCausalLM(hf_cfg)
    else:  # qwen3_moe: qk-norm + mlp.experts.* naming + moe_intermediate_size
        hf_cfg = transformers.Qwen3MoeConfig(
            num_experts=4, num_experts_per_tok=2, moe_intermediate_size=24,
            decoder_sparse_step=1, mlp_only_layers=[], norm_topk_prob=True,
            head_dim=8, **common
        )
        hf = transformers.Qwen3MoeForCausalLM(hf_cfg)

    torch.manual_seed(0)
    for p in hf.parameters():
        torch.nn.init.normal_(p, std=0.05)
    hf = hf.eval().to(torch.float32)
    hf.save_pretrained(tmp_path, safe_serialization=True)

    cfg = from_hf_config(json.loads((tmp_path / "config.json").read_text()), name=family)
    assert cfg.num_layers == 2
    params = load_hf_params(cfg, str(tmp_path), dtype="float32")

    prompt = [1, 5, 9, 42, 17, 3]
    with torch.no_grad():
        want = hf(torch.tensor([prompt])).logits[0, -1].numpy()
    got = _prefill_logits(cfg, params, prompt)
    # mixtral's HF impl drops no tokens (no capacity), and neither does
    # ours (ops/moe.py is dropless) — exact parity expected.
    np.testing.assert_allclose(got, want, rtol=4e-3, atol=4e-3)


def test_resolve_model_dir_prefers_local_dir(tmp_path):
    d = tmp_path / "ckpt"
    d.mkdir()
    assert resolve_model_dir(str(d)) == str(d)
    with pytest.raises(FileNotFoundError):
        resolve_model_dir("nonexistent/model", cache_dir=str(tmp_path))
