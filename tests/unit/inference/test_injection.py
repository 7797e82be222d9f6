"""Foreign-model injection parity tests.

Reference pattern: ``tests/unit/inference/test_inference.py`` sweeps HF
models through ``init_inference`` and compares against the unfused model.
Here tiny HF torch models (built offline from configs, random weights) are
injected into the fused TPU decode path and compared logit-for-logit.
"""

import jax
import numpy as np
import pytest
import torch

import deepspeed_tpu
from deepspeed_tpu.module_inject import AutoTP, inject_hf_model

transformers = pytest.importorskip("transformers")


def _hf_logits(model, ids):
    with torch.no_grad():
        return model(torch.tensor(ids)).logits.float().numpy()


def _hf_greedy(model, ids, n):
    ids = torch.tensor(ids)
    with torch.no_grad():
        for _ in range(n):
            logits = model(ids).logits[:, -1]
            ids = torch.cat([ids, logits.argmax(-1, keepdim=True)], dim=1)
    return ids.numpy()


@pytest.fixture(scope="module")
def tiny_gpt2():
    torch.manual_seed(0)
    cfg = transformers.GPT2Config(vocab_size=97, n_positions=64, n_embd=32,
                                  n_layer=2, n_head=4)
    return transformers.GPT2LMHeadModel(cfg).eval()


@pytest.fixture(scope="module")
def ids():
    rng = np.random.default_rng(0)
    return rng.integers(0, 97, size=(2, 12))


class TestGPT2Injection:

    def test_logits_parity(self, tiny_gpt2, ids):
        engine = deepspeed_tpu.init_inference(tiny_gpt2, dtype="float32")
        ours = np.asarray(engine(ids))[:, :, :97]
        ref = _hf_logits(tiny_gpt2, ids)
        np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=2e-4)

    def test_logits_parity_tp2(self, tiny_gpt2, ids):
        engine = deepspeed_tpu.init_inference(
            tiny_gpt2, dtype="float32", tensor_parallel={"tp_size": 2})
        assert int(engine.mesh.shape["tensor"]) == 2
        ours = np.asarray(engine(ids))[:, :, :97]
        ref = _hf_logits(tiny_gpt2, ids)
        np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=2e-4)

    def test_greedy_generate_parity(self, tiny_gpt2, ids):
        engine = deepspeed_tpu.init_inference(tiny_gpt2, dtype="float32")
        ours = np.asarray(engine.generate(ids, max_new_tokens=8))
        ref = _hf_greedy(tiny_gpt2, ids, 8)
        np.testing.assert_array_equal(ours, ref)


class TestOPTInjection:

    def test_logits_parity(self, ids):
        torch.manual_seed(1)
        cfg = transformers.OPTConfig(
            vocab_size=97, hidden_size=32, num_hidden_layers=2, ffn_dim=128,
            num_attention_heads=4, max_position_embeddings=64,
            activation_function="relu", word_embed_proj_dim=32,
            do_layer_norm_before=True)
        hf = transformers.OPTForCausalLM(cfg).eval()
        engine = deepspeed_tpu.init_inference(hf, dtype="float32")
        ours = np.asarray(engine(ids))[:, :, :97]
        ref = _hf_logits(hf, ids)
        np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=2e-4)


class TestGPTNeoInjection:

    def test_logits_parity(self, ids):
        torch.manual_seed(2)
        cfg = transformers.GPTNeoConfig(
            vocab_size=97, max_position_embeddings=64, hidden_size=32,
            num_layers=2, attention_types=[[["global"], 2]], num_heads=4)
        hf = transformers.GPTNeoForCausalLM(cfg).eval()
        engine = deepspeed_tpu.init_inference(hf, dtype="float32")
        ours = np.asarray(engine(ids))[:, :, :97]
        ref = _hf_logits(hf, ids)
        np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=2e-4)


class TestAutoTP:

    def test_tp_parser_and_specs(self):
        """Row/column classification on an arbitrary pytree (the reference's
        tp_parser finds all-reduce points, ``auto_tp.py:13``)."""
        params = {
            "wte": np.zeros((128, 16)),
            "h0": {
                "qkv_w": np.zeros((16, 48)), "qkv_b": np.zeros((48,)),
                "out_w": np.zeros((16, 16)), "out_b": np.zeros((16,)),
                "ln_g": np.zeros((16,)),
            },
        }
        rows = AutoTP.tp_parser(params)
        assert rows == ["h0/out_w"]
        from jax.sharding import PartitionSpec as P
        specs = AutoTP(mp_size=2).partition_specs(params)
        assert specs["wte"] == P("tensor", None)
        assert specs["h0"]["qkv_w"] == P(None, "tensor")
        assert specs["h0"]["qkv_b"] == P("tensor")       # column bias sharded
        assert specs["h0"]["out_w"] == P("tensor", None)  # row-parallel
        assert specs["h0"]["out_b"] == P()                # row bias replicated
        assert specs["h0"]["ln_g"] == P()

    def test_stacked_specs(self):
        """Scan-stacked [L, ...] leaves keep the layer dim unsharded."""
        from jax.sharding import PartitionSpec as P
        params = {"blocks": {"fc_w": np.zeros((4, 16, 64)),
                             "proj_w": np.zeros((4, 64, 16))}}
        specs = AutoTP().partition_specs(params)
        assert specs["blocks"]["fc_w"] == P(None, None, "tensor")
        assert specs["blocks"]["proj_w"] == P(None, "tensor", None)


class TestAutoTPBiasAndValidation:

    def test_stacked_bias_links_to_weight(self):
        """A scan-stacked bias [L, dim] is a bias, not a 2-D weight: column
        biases shard on the trailing dim, row biases stay replicated."""
        from jax.sharding import PartitionSpec as P
        params = {"blocks": {
            "qkv_w": np.zeros((4, 16, 48)), "qkv_b": np.zeros((4, 48)),
            "out_w": np.zeros((4, 16, 16)), "out_b": np.zeros((4, 16)),
        }}
        specs = AutoTP().partition_specs(params)
        assert specs["blocks"]["qkv_b"] == P(None, "tensor")
        assert specs["blocks"]["out_b"] == P()

    def test_mp_size_divisibility_validated(self):
        params = {"fc_w": np.zeros((16, 50))}    # 50 % 4 != 0
        with pytest.raises(ValueError, match="not divisible"):
            AutoTP(mp_size=4).partition_specs(params)


class TestInjectionFixes:

    def test_untied_lm_head_is_loaded(self):
        """tie_word_embeddings=False checkpoints keep their distinct head."""
        torch.manual_seed(1)
        cfg = transformers.GPT2Config(vocab_size=97, n_positions=64, n_embd=32,
                                      n_layer=2, n_head=4,
                                      tie_word_embeddings=False)
        hf = transformers.GPT2LMHeadModel(cfg).eval()
        # make the head distinct from wte for sure
        with torch.no_grad():
            hf.lm_head.weight.add_(torch.randn_like(hf.lm_head.weight))
        ids = np.array([[5, 11, 2, 7, 3, 1, 0, 9]], np.int64)
        engine = deepspeed_tpu.init_inference(hf, dtype="fp32")
        ours = np.asarray(engine.forward(ids), np.float32)[:, :, :97]
        ref = _hf_logits(hf, ids)
        np.testing.assert_allclose(ours, ref, atol=2e-3, rtol=2e-3)

    def test_activation_function_respected(self):
        """activation_function='relu' must not silently become gelu."""
        torch.manual_seed(2)
        cfg = transformers.GPT2Config(vocab_size=97, n_positions=64, n_embd=32,
                                      n_layer=2, n_head=4,
                                      activation_function="relu")
        hf = transformers.GPT2LMHeadModel(cfg).eval()
        ids = np.array([[5, 11, 2, 7]], np.int64)
        engine = deepspeed_tpu.init_inference(hf, dtype="fp32")
        ours = np.asarray(engine.forward(ids), np.float32)[:, :, :97]
        np.testing.assert_allclose(ours, _hf_logits(hf, ids), atol=2e-3, rtol=2e-3)

    def test_unsupported_activation_raises(self):
        from deepspeed_tpu.module_inject.policies import _map_activation
        with pytest.raises(NotImplementedError, match="silu"):
            _map_activation("silu")

    def test_caller_params_not_overwritten(self):
        """InferenceEngine(hf_model, params=...) honors the caller's params."""
        torch.manual_seed(3)
        cfg = transformers.GPT2Config(vocab_size=97, n_positions=64, n_embd=32,
                                      n_layer=2, n_head=4)
        hf = transformers.GPT2LMHeadModel(cfg).eval()
        from deepspeed_tpu.module_inject import inject_hf_model
        _, params = inject_hf_model(hf)
        import jax
        zeroed = jax.tree.map(lambda a: np.zeros_like(a), params)
        engine = deepspeed_tpu.init_inference(hf, dtype="fp32", params=zeroed)
        ids = np.array([[5, 11]], np.int64)
        out = np.asarray(engine.forward(ids), np.float32)
        assert np.allclose(out, out[0, 0, 0])    # all-zero params → flat logits


@pytest.fixture(scope="module")
def tiny_bloom():
    torch.manual_seed(4)
    cfg = transformers.BloomConfig(vocab_size=97, hidden_size=32, n_layer=2,
                                   n_head=4)
    return transformers.BloomForCausalLM(cfg).eval()


@pytest.fixture(scope="module")
def tiny_llama():
    torch.manual_seed(5)
    cfg = transformers.LlamaConfig(vocab_size=97, hidden_size=32,
                                   num_hidden_layers=2, num_attention_heads=4,
                                   num_key_value_heads=4, intermediate_size=64,
                                   max_position_embeddings=64)
    return transformers.LlamaForCausalLM(cfg).eval()


IDS2 = np.array([[5, 11, 2, 7, 3, 1, 0, 9]], np.int64)


class TestBloomInjection:
    def test_logits_parity(self, tiny_bloom):
        engine = deepspeed_tpu.init_inference(tiny_bloom, dtype="fp32")
        ours = np.asarray(engine.forward(IDS2), np.float32)[:, :, :97]
        ref = _hf_logits(tiny_bloom, IDS2)
        np.testing.assert_allclose(ours, ref, atol=3e-3, rtol=3e-3)

    def test_greedy_generate_parity(self, tiny_bloom):
        engine = deepspeed_tpu.init_inference(tiny_bloom, dtype="fp32")
        ours = np.asarray(engine.generate(IDS2, max_new_tokens=6))
        ref = _hf_greedy(tiny_bloom, IDS2, 6)
        np.testing.assert_array_equal(ours, ref)


class TestLlamaInjection:
    def test_logits_parity(self, tiny_llama):
        engine = deepspeed_tpu.init_inference(tiny_llama, dtype="fp32")
        ours = np.asarray(engine.forward(IDS2), np.float32)[:, :, :97]
        ref = _hf_logits(tiny_llama, IDS2)
        # tight: any rope-pairing mistake shows up far above fp32 noise
        np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=2e-4)

    def test_greedy_generate_parity(self, tiny_llama):
        engine = deepspeed_tpu.init_inference(tiny_llama, dtype="fp32")
        ours = np.asarray(engine.generate(IDS2, max_new_tokens=6))
        ref = _hf_greedy(tiny_llama, IDS2, 6)
        np.testing.assert_array_equal(ours, ref)


class TestLlamaGQA:
    @pytest.fixture(scope="class")
    def tiny_gqa(self):
        torch.manual_seed(6)
        cfg = transformers.LlamaConfig(vocab_size=97, hidden_size=32,
                                       num_hidden_layers=2,
                                       num_attention_heads=4,
                                       num_key_value_heads=2,   # GQA
                                       intermediate_size=64,
                                       max_position_embeddings=64)
        return transformers.LlamaForCausalLM(cfg).eval()

    def test_logits_parity(self, tiny_gqa):
        engine = deepspeed_tpu.init_inference(tiny_gqa, dtype="fp32")
        assert engine.module.cfg.kv_heads == 2
        ours = np.asarray(engine.forward(IDS2), np.float32)[:, :, :97]
        ref = _hf_logits(tiny_gqa, IDS2)
        np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=2e-4)

    def test_greedy_generate_and_cache_shape(self, tiny_gqa):
        engine = deepspeed_tpu.init_inference(tiny_gqa, dtype="fp32")
        ours = np.asarray(engine.generate(IDS2, max_new_tokens=6))
        ref = _hf_greedy(tiny_gqa, IDS2, 6)
        np.testing.assert_array_equal(ours, ref)
        # the cache stores only the kv heads (the GQA memory win);
        # batch must divide the active data axis for placement
        cache = engine.module.init_cache(8, 32)
        cfg = engine.module.cfg
        assert cache["k"].shape[3] == 2 * cfg.head_dim   # [L, B, T, Hkv*D]

    def test_logits_parity_tp2(self, tiny_gqa):
        """TP x GQA: kv heads shard over the tensor axis."""
        from deepspeed_tpu.parallel import mesh as mesh_lib
        mesh_lib.reset_mesh()
        try:
            engine = deepspeed_tpu.init_inference(
                tiny_gqa, dtype="fp32", tensor_parallel={"tp_size": 2})
            ours = np.asarray(engine.forward(IDS2), np.float32)[:, :, :97]
            np.testing.assert_allclose(ours, _hf_logits(tiny_gqa, IDS2),
                                       atol=2e-4, rtol=2e-4)
        finally:
            mesh_lib.reset_mesh()


@pytest.fixture(scope="module")
def tiny_gptj():
    torch.manual_seed(1)
    cfg = transformers.GPTJConfig(vocab_size=97, n_positions=64, n_embd=32,
                                  n_layer=2, n_head=4, rotary_dim=4,
                                  tie_word_embeddings=False)
    return transformers.GPTJForCausalLM(cfg).eval()


@pytest.fixture(scope="module")
def tiny_gptneox():
    torch.manual_seed(2)
    cfg = transformers.GPTNeoXConfig(vocab_size=97, max_position_embeddings=64,
                                     hidden_size=32, num_hidden_layers=2,
                                     num_attention_heads=4, intermediate_size=128,
                                     rotary_pct=0.5, use_parallel_residual=True)
    return transformers.GPTNeoXForCausalLM(cfg).eval()


class TestGPTJInjection:
    """GPT-J: interleaved partial rotary + single-LN parallel residual +
    biased untied head (reference module_inject/containers/gptj.py)."""

    def test_logits_parity(self, tiny_gptj, ids):
        engine = deepspeed_tpu.init_inference(tiny_gptj, dtype="float32")
        ours = np.asarray(engine(ids))[:, :, :97]
        ref = _hf_logits(tiny_gptj, ids)
        np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-4)

    def test_greedy_parity(self, tiny_gptj, ids):
        engine = deepspeed_tpu.init_inference(tiny_gptj, dtype="float32")
        ours = np.asarray(engine.generate(ids[:1], max_new_tokens=6))
        ref = _hf_greedy(tiny_gptj, ids[:1], 6)
        np.testing.assert_array_equal(ours, ref)

    def test_logits_parity_tp2(self, tiny_gptj, ids):
        engine = deepspeed_tpu.init_inference(
            tiny_gptj, dtype="float32", tensor_parallel={"tp_size": 2})
        ours = np.asarray(engine(ids))[:, :, :97]
        ref = _hf_logits(tiny_gptj, ids)
        np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-4)


class TestGPTNeoXInjection:
    """GPT-NeoX/Pythia: head-interleaved fused qkv + partial rotary +
    parallel residual (reference module_inject/containers/gptneox.py)."""

    def test_logits_parity(self, tiny_gptneox, ids):
        engine = deepspeed_tpu.init_inference(tiny_gptneox, dtype="float32")
        ours = np.asarray(engine(ids))[:, :, :97]
        ref = _hf_logits(tiny_gptneox, ids)
        np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-4)

    def test_greedy_parity(self, tiny_gptneox, ids):
        engine = deepspeed_tpu.init_inference(tiny_gptneox, dtype="float32")
        ours = np.asarray(engine.generate(ids[:1], max_new_tokens=6))
        ref = _hf_greedy(tiny_gptneox, ids[:1], 6)
        np.testing.assert_array_equal(ours, ref)

    def test_sequential_variant(self, ids):
        torch.manual_seed(3)
        cfg = transformers.GPTNeoXConfig(
            vocab_size=97, max_position_embeddings=64, hidden_size=32,
            num_hidden_layers=2, num_attention_heads=4, intermediate_size=128,
            rotary_pct=0.25, use_parallel_residual=False)
        model = transformers.GPTNeoXForCausalLM(cfg).eval()
        engine = deepspeed_tpu.init_inference(model, dtype="float32")
        ours = np.asarray(engine(ids))[:, :, :97]
        ref = _hf_logits(model, ids)
        np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-4)


class TestBertInjection:
    """Encoder injection (reference module_inject/containers/bert.py):
    BertForMaskedLM served as fixed-length MLM logits through
    init_inference — the first encoder-family policy."""

    @pytest.fixture(scope="class")
    def tiny_bert(self):
        torch.manual_seed(4)
        cfg = transformers.BertConfig(vocab_size=97, hidden_size=32,
                                      num_hidden_layers=2, num_attention_heads=4,
                                      intermediate_size=128,
                                      max_position_embeddings=64)
        return transformers.BertForMaskedLM(cfg).eval()

    def test_mlm_logits_parity(self, tiny_bert, ids):
        engine = deepspeed_tpu.init_inference(tiny_bert, dtype="float32")
        ours = np.asarray(engine(ids))[:, :, :97]
        with torch.no_grad():
            ref = tiny_bert(torch.tensor(ids)).logits.float().numpy()
        np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-4)

    def test_mlm_logits_parity_tp2(self, tiny_bert, ids):
        engine = deepspeed_tpu.init_inference(
            tiny_bert, dtype="float32", tensor_parallel={"tp_size": 2})
        ours = np.asarray(engine(ids))[:, :, :97]
        with torch.no_grad():
            ref = tiny_bert(torch.tensor(ids)).logits.float().numpy()
        np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-4)

    def test_padded_batch_attention_mask(self, tiny_bert, ids):
        """Padded serving: pad tokens must not perturb real tokens' MLM
        logits (the encoder's standard batched-serving input)."""
        engine = deepspeed_tpu.init_inference(tiny_bert, dtype="float32")
        padded = np.concatenate([ids, np.zeros((2, 4), ids.dtype)], axis=1)
        mask = np.concatenate([np.ones_like(ids), np.zeros((2, 4), ids.dtype)],
                              axis=1)
        ours = np.asarray(engine.forward(padded, attention_mask=mask))
        with torch.no_grad():
            ref = tiny_bert(torch.tensor(padded),
                            attention_mask=torch.tensor(mask)).logits
        np.testing.assert_allclose(ours[:, :12, :97], ref.numpy()[:, :12],
                                   atol=3e-4, rtol=3e-4)


class TestDistilBertInjection:
    """DistilBERT MLM through the fused encoder (no token-type embeddings,
    separate q/k/v linears concatenated into fused qkv)."""

    @pytest.fixture(scope="class")
    def tiny_distilbert(self):
        torch.manual_seed(7)
        cfg = transformers.DistilBertConfig(
            vocab_size=97, dim=32, n_layers=2, n_heads=4, hidden_dim=64,
            max_position_embeddings=64)
        return transformers.DistilBertForMaskedLM(cfg).eval()

    def test_mlm_logits_parity(self, tiny_distilbert, ids):
        engine = deepspeed_tpu.init_inference(tiny_distilbert, dtype="float32")
        ours = np.asarray(engine(ids))[:, :, :97]
        ref = _hf_logits(tiny_distilbert, ids)
        np.testing.assert_allclose(ours, ref, atol=2e-4, rtol=2e-4)


class TestCLIPInjection:
    """Both CLIP towers (reference module_inject/containers/clip.py) served
    as hidden states through init_inference."""

    def test_text_tower_parity(self, ids):
        torch.manual_seed(8)
        cfg = transformers.CLIPTextConfig(
            vocab_size=99, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=32)
        hf = transformers.CLIPTextModel(cfg).eval()
        engine = deepspeed_tpu.init_inference(hf, dtype="float32")
        ours = np.asarray(engine(ids))
        with torch.no_grad():
            ref = hf(torch.tensor(ids)).last_hidden_state.float().numpy()
        np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-4)

    def test_text_pooled_legacy_eos(self):
        """Legacy configs (eos_token_id=2, the HF default) pool at
        input_ids.argmax — HF's special case, matched exactly."""
        torch.manual_seed(10)
        cfg = transformers.CLIPTextConfig(
            vocab_size=99, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=32, eos_token_id=2)
        hf = transformers.CLIPTextModel(cfg).eval()
        engine = deepspeed_tpu.init_inference(hf, dtype="float32")
        ids = np.random.default_rng(4).integers(3, 99, (2, 12))
        pooled = np.asarray(jax.jit(engine.module.pooled)(engine.params, ids))
        with torch.no_grad():
            ref = hf(torch.tensor(ids)).pooler_output.float().numpy()
        np.testing.assert_allclose(pooled, ref, atol=3e-4, rtol=3e-4)

    @pytest.mark.parametrize("act", ["quick_gelu", "gelu"])
    def test_vision_tower_parity(self, act):
        torch.manual_seed(9)
        cfg = transformers.CLIPVisionConfig(
            hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
            intermediate_size=64, image_size=32, patch_size=8,
            hidden_act=act)
        hf = transformers.CLIPVisionModel(cfg).eval()
        engine = deepspeed_tpu.init_inference(hf, dtype="float32")
        rng = np.random.default_rng(3)
        pixels = rng.standard_normal((2, 3, 32, 32)).astype(np.float32)
        ours = np.asarray(engine(pixels))
        with torch.no_grad():
            out = hf(torch.tensor(pixels))
        ref = out.last_hidden_state.float().numpy()
        np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-4)
        # pooled = post-LN CLS row (HF pooler_output)
        pooled = np.asarray(jax.jit(engine.module.pooled)(engine.params, pixels))
        np.testing.assert_allclose(pooled, out.pooler_output.float().numpy(),
                                   atol=3e-4, rtol=3e-4)
