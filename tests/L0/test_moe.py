"""Mixture-of-experts: routing, SwitchMLP, expert parallelism.

No reference counterpart (juncongmoo/apex has no MoE — SURVEY.md §2.3);
tests follow the house style of test_transformer_tp.py: numerics vs
hand-computed references on a single device, then ep-sharded vs local
equivalence on the virtual mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.testing import shard_map
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.moe import (
    SwitchMLP,
    compute_routing,
    is_expert_param,
    moe_loss_from_variables,
)
from apex_tpu.transformer.moe.router import expert_capacity


class TestRouting:
    def test_top1_dispatch_and_capacity_drop(self):
        # 4 tokens, 2 experts; tokens 0,1,2 prefer expert 0, token 3
        # prefers expert 1. Capacity 2 -> token 2 is dropped.
        logits = jnp.array([[2.0, 0.0],
                            [2.0, 0.0],
                            [2.0, 0.0],
                            [0.0, 2.0]])
        r = compute_routing(logits, top_k=1, capacity=2)
        d = np.asarray(r.dispatch_mask)
        # tokens 0,1 fill expert-0 slots 0,1 in arrival order
        assert d[0, 0, 0] == 1 and d[1, 0, 1] == 1
        assert d[2].sum() == 0  # dropped
        assert d[3, 1, 0] == 1
        probs = np.asarray(r.probs)
        c = np.asarray(r.combine_weights)
        np.testing.assert_allclose(c[0, 0, 0], probs[0, 0], rtol=1e-6)
        np.testing.assert_allclose(c[3, 1, 0], probs[3, 1], rtol=1e-6)
        assert c[2].sum() == 0
        np.testing.assert_allclose(float(r.dropped_fraction), 0.25)

    def test_top2_normalized_weights(self):
        logits = jnp.array([[1.0, 0.5, -1.0],
                            [0.2, 1.4, 0.3]])
        r = compute_routing(logits, top_k=2, capacity=2)
        # each token keeps both choices; normalized weights sum to 1
        w = np.asarray(r.combine_weights).sum(axis=(1, 2))
        np.testing.assert_allclose(w, [1.0, 1.0], rtol=1e-5)
        assert np.asarray(r.dispatch_mask).sum() == 4

    def test_aux_loss_balanced_is_one(self):
        # perfectly balanced hard assignments with near-uniform probs:
        # f_e = 1/E and P_e ~ 1/E -> aux = E * sum f*P ~ 1
        eps = 1e-3
        logits = jnp.array([[eps, 0.0], [0.0, eps]] * 8)
        r = compute_routing(logits, top_k=1, capacity=16)
        np.testing.assert_allclose(float(r.aux_loss), 1.0, atol=1e-3)

    def test_aux_loss_penalizes_collapse(self):
        all_to_one = jnp.tile(jnp.array([[4.0, 0.0]]), (16, 1))
        r = compute_routing(all_to_one, top_k=1, capacity=16)
        assert float(r.aux_loss) > 1.5  # E * 1 * P_0, P_0 ~ 0.98

    def test_z_loss(self):
        logits = jnp.zeros((4, 4))
        r = compute_routing(logits, top_k=1, capacity=4)
        np.testing.assert_allclose(float(r.z_loss), np.log(4.0) ** 2,
                                   rtol=1e-5)

    def test_capacity_rounding(self):
        assert expert_capacity(1024, 8, 1, 1.25) == 160
        # tiny raw capacities round up to the TPU lane multiple too
        assert expert_capacity(16, 8, 1, 1.0) == 8


class TestExpertChoiceRouting:
    def test_each_expert_fills_capacity(self):
        from apex_tpu.transformer.moe import compute_expert_choice_routing

        logits = jnp.asarray(np.random.RandomState(0).randn(8, 3),
                             jnp.float32)
        r = compute_expert_choice_routing(logits, capacity=2)
        d = np.asarray(r.dispatch_mask)  # [T, E, C]
        # every expert fills exactly its 2 slots — balanced by construction
        np.testing.assert_array_equal(d.sum(axis=(0, 2)), [2, 2, 2])
        assert float(r.aux_loss) == 0.0
        # combine weight at a filled slot equals that token's prob
        probs = np.asarray(r.probs)
        c = np.asarray(r.combine_weights)
        t, e, s = np.argwhere(d > 0)[0]
        np.testing.assert_allclose(c[t, e, s], probs[t, e], rtol=1e-6)

    def test_expert_picks_its_top_tokens(self):
        from apex_tpu.transformer.moe import compute_expert_choice_routing

        # expert 0 strongly prefers tokens 1 and 3
        logits = jnp.array([[0.0, 1.0],
                            [5.0, 0.0],
                            [0.1, 1.0],
                            [4.0, 0.0]])
        r = compute_expert_choice_routing(logits, capacity=2)
        d = np.asarray(r.dispatch_mask)
        assert d[1, 0].sum() == 1 and d[3, 0].sum() == 1
        # tokens 0 and 2 were not chosen by expert 0
        assert d[0, 0].sum() == 0 and d[2, 0].sum() == 0

    def test_dropped_fraction_counts_unpicked_tokens(self):
        from apex_tpu.transformer.moe import compute_expert_choice_routing

        # 4 tokens, 1 expert, capacity 2 -> 2 tokens unpicked
        logits = jnp.asarray(np.random.RandomState(1).randn(4, 1),
                             jnp.float32)
        r = compute_expert_choice_routing(logits, capacity=2)
        np.testing.assert_allclose(float(r.dropped_fraction), 0.5)

    def test_switch_mlp_expert_choice_grads(self):
        layer = SwitchMLP(hidden_size=16, ffn_hidden_size=32, num_experts=4,
                          capacity_factor=2.0, router_type="expert_choice",
                          compute_dtype=jnp.float32)
        x = jnp.asarray(np.random.RandomState(0).randn(8, 2, 16), jnp.float32)
        params = layer.init(jax.random.PRNGKey(0), x)["params"]

        def loss(p):
            return jnp.sum(layer.apply({"params": p}, x,
                                       mutable=["moe_losses"])[0] ** 2)

        g = jax.grad(loss)(params)
        assert float(jnp.abs(g["router"]["gate_weight"]).sum()) > 0
        assert float(jnp.abs(g["experts"]["w1"]).sum()) > 0

    @pytest.mark.slow  # tier-1 budget (round 18): EP-vs-local parity
    # is covered by test_ep4_matches_local and the expert-choice
    # routing by test_switch_mlp_expert_choice_grads
    def test_expert_choice_ep_matches_local(self):
        E, ep = 4, 4
        rng = np.random.RandomState(7)
        params = {
            "router": {"gate_weight": jnp.asarray(
                rng.randn(16, E) * 0.2, jnp.float32)},
            "experts": {
                "w1": jnp.asarray(rng.randn(E, 16, 32) * 0.1, jnp.float32),
                "b1": jnp.zeros((E, 32), jnp.float32),
                "w2": jnp.asarray(rng.randn(E, 32, 16) * 0.1, jnp.float32),
                "b2": jnp.zeros((E, 16), jnp.float32),
            },
        }
        x = jnp.asarray(rng.randn(8, ep, 16), jnp.float32)
        parallel_state.initialize_model_parallel(
            expert_model_parallel_size_=ep, devices=jax.devices()[:ep])
        mesh = parallel_state.get_mesh()
        layer = SwitchMLP(hidden_size=16, ffn_hidden_size=32, num_experts=E,
                          capacity_factor=2.0, router_type="expert_choice",
                          compute_dtype=jnp.float32)

        saved = parallel_state._EXPERT_MODEL_PARALLEL_WORLD_SIZE
        parallel_state._EXPERT_MODEL_PARALLEL_WORLD_SIZE = 1
        ref = jnp.concatenate(
            [layer.apply({"params": params}, x[:, i:i + 1])
             for i in range(ep)], axis=1)
        parallel_state._EXPERT_MODEL_PARALLEL_WORLD_SIZE = saved

        pspec = {"router": {"gate_weight": P()},
                 "experts": {k: P("ep") for k in params["experts"]}}

        @shard_map(mesh=mesh, in_specs=(pspec, P(None, "ep", None)),
                   out_specs=P(None, "ep", None))
        def run(p, xs):
            return layer.apply({"params": p}, xs)

        np.testing.assert_allclose(np.asarray(run(params, x)),
                                   np.asarray(ref), rtol=2e-4, atol=2e-4)

    def test_unknown_router_type_raises(self):
        layer = SwitchMLP(hidden_size=16, ffn_hidden_size=32, num_experts=2,
                          router_type="nonsense", compute_dtype=jnp.float32)
        x = jnp.ones((4, 1, 16))
        with pytest.raises(ValueError, match="router_type"):
            layer.init(jax.random.PRNGKey(0), x)

    @pytest.mark.slow  # tier-1 budget: routing units above cover EC
    def test_gpt_expert_choice_config(self):
        from apex_tpu.models import GPTModel, TransformerConfig
        from apex_tpu.models.gpt import gpt_loss_fn

        parallel_state.destroy_model_parallel()
        cfg = TransformerConfig(
            hidden_size=32, num_layers=2, num_attention_heads=4,
            vocab_size=64, max_position_embeddings=16,
            compute_dtype=jnp.float32, use_flash_attention=False,
            num_moe_experts=4, moe_router_type="expert_choice")
        model = GPTModel(cfg)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 64, size=(2, 16)))
        variables = model.init(jax.random.PRNGKey(0), tokens)

        def loss_fn(p):
            logits, _ = model.apply({"params": p}, tokens,
                                    mutable=["moe_losses"])
            return gpt_loss_fn(logits, jnp.roll(tokens, -1, axis=-1))

        loss, g = jax.value_and_grad(loss_fn)(variables["params"])
        assert np.isfinite(float(loss))
        router_g = g["transformer"]["layer_0"]["mlp"]["router"]["gate_weight"]
        assert float(jnp.abs(router_g).sum()) > 0


class TestSwitchMLP:
    def _make(self, num_experts=4, top_k=1, capacity=64, hidden=16, ffn=32):
        layer = SwitchMLP(hidden_size=hidden, ffn_hidden_size=ffn,
                          num_experts=num_experts, top_k=top_k,
                          capacity_factor=8.0,  # ample: no drops
                          compute_dtype=jnp.float32)
        x = jnp.asarray(np.random.RandomState(0).randn(8, 2, hidden),
                        jnp.float32)
        params = layer.init(jax.random.PRNGKey(0), x)["params"]
        return layer, params, x

    def test_single_expert_equals_dense_mlp(self):
        """E=1 with ample capacity routes every token through the one
        expert with weight 1 — output must equal the plain FFN."""
        layer, params, x = self._make(num_experts=1)
        out = layer.apply({"params": params}, x)
        e = params["experts"]
        t = x.reshape(-1, x.shape[-1])
        h1 = t @ np.asarray(e["w1"])[0] + np.asarray(e["b1"])[0]
        ref = jax.nn.gelu(h1) @ np.asarray(e["w2"])[0] + np.asarray(e["b2"])[0]
        np.testing.assert_allclose(np.asarray(out).reshape(-1, x.shape[-1]),
                                   np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_moe_losses_sown(self):
        layer, params, x = self._make()
        out, mut = layer.apply({"params": params}, x,
                               mutable=["moe_losses"])
        total = moe_loss_from_variables(mut, aux_loss_coeff=1.0)
        assert float(total) > 0
        assert out.shape == x.shape

    def test_grads_flow_to_router_and_experts(self):
        layer, params, x = self._make()

        def loss(p):
            out, mut = layer.apply({"params": p}, x, mutable=["moe_losses"])
            return jnp.sum(out ** 2) + moe_loss_from_variables(mut)

        g = jax.grad(loss)(params)
        assert float(jnp.abs(g["router"]["gate_weight"]).sum()) > 0
        assert float(jnp.abs(g["experts"]["w1"]).sum()) > 0

    def test_router_jitter_needs_rng_stream(self):
        """moe_jitter_eps perturbs routing only when a 'jitter' rng is
        supplied; without the stream the layer stays deterministic."""
        hidden = 16
        layer = SwitchMLP(hidden_size=hidden, ffn_hidden_size=32,
                          num_experts=4, capacity_factor=8.0,
                          jitter_eps=0.3, compute_dtype=jnp.float32)
        x = jnp.asarray(np.random.RandomState(3).randn(8, 2, hidden),
                        jnp.float32)
        params = layer.init(jax.random.PRNGKey(0), x)["params"]
        base = layer.apply({"params": params}, x)
        again = layer.apply({"params": params}, x)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(again))
        jittered = layer.apply({"params": params}, x,
                               rngs={"jitter": jax.random.PRNGKey(9)})
        assert not np.allclose(np.asarray(base), np.asarray(jittered))

    def test_is_expert_param(self):
        assert is_expert_param("transformer/layer_0/mlp/experts/w1")
        assert not is_expert_param("transformer/layer_0/mlp/router/gate_weight")
        # segment match, not substring: dense modules merely containing
        # the word must not be classified as expert shards
        assert not is_expert_param("blk/experts_gate/kernel")
        assert not is_expert_param("blk/shared_experts_norm/scale")

    @pytest.mark.slow
    def test_jitter_key_forced_tp_uniform(self):
        """Even an adversarial per-tp-rank jitter key (the dropout-key
        discipline) must yield identical routing on every tp rank."""
        parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=2, devices=jax.devices()[:2])
        mesh = parallel_state.get_mesh()
        layer = SwitchMLP(hidden_size=16, ffn_hidden_size=32, num_experts=2,
                          capacity_factor=4.0, jitter_eps=0.3,
                          compute_dtype=jnp.float32)
        x = jnp.asarray(np.random.RandomState(11).randn(8, 2, 16),
                        jnp.float32)

        @shard_map(mesh=mesh, in_specs=P(), out_specs=P("tp"))
        def run(xs):
            params = layer.init(jax.random.PRNGKey(0), xs)["params"]
            key = jax.random.fold_in(jax.random.PRNGKey(5),
                                     jax.lax.axis_index("tp"))
            return layer.apply({"params": params}, xs,
                               rngs={"jitter": key})[None]

        outs = np.asarray(run(x))
        np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6)


class TestExpertParallel:
    """ep-sharded SwitchMLP == per-shard local runs (the ep axis only
    moves expert shards; routing is per-device over local tokens)."""

    def _params_and_input(self, hidden=16, ffn=32, E=4, seq=8, b=4):
        rng = np.random.RandomState(7)
        params = {
            "router": {"gate_weight": jnp.asarray(
                rng.randn(hidden, E) * 0.2, jnp.float32)},
            "experts": {
                "w1": jnp.asarray(rng.randn(E, hidden, ffn) * 0.1, jnp.float32),
                "b1": jnp.zeros((E, ffn), jnp.float32),
                "w2": jnp.asarray(rng.randn(E, ffn, hidden) * 0.1, jnp.float32),
                "b2": jnp.zeros((E, hidden), jnp.float32),
            },
        }
        x = jnp.asarray(rng.randn(seq, b, hidden), jnp.float32)
        return params, x

    def test_ep4_matches_local(self):
        E, ep = 4, 4
        params, x = self._params_and_input(E=E, b=ep)
        parallel_state.initialize_model_parallel(
            expert_model_parallel_size_=ep, devices=jax.devices()[:ep])
        mesh = parallel_state.get_mesh()
        assert "ep" in mesh.shape and mesh.shape["ep"] == ep

        layer = SwitchMLP(hidden_size=16, ffn_hidden_size=32, num_experts=E,
                          capacity_factor=8.0, compute_dtype=jnp.float32)

        # reference: each batch shard routed independently with all experts
        parallel_state_ep = parallel_state._EXPERT_MODEL_PARALLEL_WORLD_SIZE
        parallel_state._EXPERT_MODEL_PARALLEL_WORLD_SIZE = 1
        ref = jnp.concatenate(
            [layer.apply({"params": params}, x[:, i:i + 1])
             for i in range(ep)], axis=1)
        parallel_state._EXPERT_MODEL_PARALLEL_WORLD_SIZE = parallel_state_ep

        pspec = {"router": {"gate_weight": P()},
                 "experts": {k: P("ep") for k in params["experts"]}}

        @shard_map(mesh=mesh,
                   in_specs=(pspec, P(None, "ep", None)),
                   out_specs=P(None, "ep", None))
        def run(p, xs):
            return layer.apply({"params": p}, xs)

        out = run(params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.slow  # tier-1 budget: ep4_matches_local covers the parity
    def test_ep_grads_match_local(self):
        E, ep = 4, 4
        params, x = self._params_and_input(E=E, b=ep)
        parallel_state.initialize_model_parallel(
            expert_model_parallel_size_=ep, devices=jax.devices()[:ep])
        mesh = parallel_state.get_mesh()
        layer = SwitchMLP(hidden_size=16, ffn_hidden_size=32, num_experts=E,
                          capacity_factor=8.0, compute_dtype=jnp.float32)

        def local_loss(p, xs):
            return jnp.sum(layer.apply({"params": p}, xs) ** 2)

        # reference: sum of per-shard losses/grads with ep disabled
        saved = parallel_state._EXPERT_MODEL_PARALLEL_WORLD_SIZE
        parallel_state._EXPERT_MODEL_PARALLEL_WORLD_SIZE = 1
        ref_grads = jax.tree_util.tree_map(
            lambda *g: sum(g),
            *[jax.grad(local_loss)(params, x[:, i:i + 1]) for i in range(ep)])
        parallel_state._EXPERT_MODEL_PARALLEL_WORLD_SIZE = saved

        pspec = {"router": {"gate_weight": P()},
                 "experts": {k: P("ep") for k in params["experts"]}}

        @shard_map(mesh=mesh,
                   in_specs=(pspec, P(None, "ep", None)),
                   out_specs=pspec)
        def grads(p, xs):
            g = jax.grad(local_loss)(p, xs)
            # dense params replicate over ep: grad sync is the dp x ep
            # reduction (get_data_parallel_axes) — here just ep.
            g["router"]["gate_weight"] = jax.lax.psum(
                g["router"]["gate_weight"], "ep")
            return g

        g = grads(params, x)
        np.testing.assert_allclose(np.asarray(g["router"]["gate_weight"]),
                                   np.asarray(ref_grads["router"]["gate_weight"]),
                                   rtol=2e-4, atol=2e-4)
        for k in ("w1", "b1", "w2", "b2"):
            np.testing.assert_allclose(np.asarray(g["experts"][k]),
                                       np.asarray(ref_grads["experts"][k]),
                                       rtol=2e-4, atol=2e-4)


class TestParallelStateEP:
    def test_ep_grid(self):
        parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=2, expert_model_parallel_size_=2,
            devices=jax.devices()[:8])
        assert parallel_state.get_expert_model_parallel_world_size() == 2
        assert parallel_state.get_data_parallel_world_size() == 2
        assert parallel_state.get_data_parallel_axes() == ("dp", "ep")
        mesh = parallel_state.get_mesh()
        assert mesh.shape == {"pp": 1, "dp": 2, "ep": 2, "tp": 2}

    def test_ep_default_absent(self):
        parallel_state.initialize_model_parallel(devices=jax.devices()[:8])
        assert parallel_state.get_data_parallel_axes() == ("dp",)
        assert "ep" not in parallel_state.get_mesh().shape

    def test_bad_ep_grid_raises(self):
        with pytest.raises(RuntimeError):
            parallel_state.initialize_model_parallel(
                expert_model_parallel_size_=3, devices=jax.devices()[:8])


class TestSequenceParallelMoE:
    @pytest.mark.slow
    def test_sp_matches_non_sp_on_tp_mesh(self):
        """SwitchMLP under sequence parallelism (seq-sharded input,
        gather on entry / scatter on exit) == the non-SP layer on the
        full sequence, for both outputs and parameter gradients."""
        TP, SEQ, B, HID = 4, 8, 2, 16
        parallel_state.destroy_model_parallel()
        parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=TP, devices=jax.devices()[:TP])
        mesh = parallel_state.get_mesh()
        rng = np.random.RandomState(5)
        params = {
            "router": {"gate_weight": jnp.asarray(
                rng.randn(HID, 2) * 0.2, jnp.float32)},
            "experts": {
                "w1": jnp.asarray(rng.randn(2, HID, 32) * 0.1, jnp.float32),
                "b1": jnp.zeros((2, 32), jnp.float32),
                "w2": jnp.asarray(rng.randn(2, 32, HID) * 0.1, jnp.float32),
                "b2": jnp.zeros((2, HID), jnp.float32),
            },
        }
        x = jnp.asarray(rng.randn(SEQ, B, HID), jnp.float32)

        # ffn shards over tp; experts replicated over... E=2 local (ep=1)
        pspec = {"router": {"gate_weight": P()},
                 "experts": {"w1": P(None, None, "tp"), "b1": P(None, "tp"),
                             "w2": P(None, "tp", None), "b2": P()}}

        def make(sp):
            return SwitchMLP(hidden_size=HID, ffn_hidden_size=32,
                             num_experts=2, capacity_factor=8.0,
                             compute_dtype=jnp.float32,
                             sequence_parallel_enabled=sp)

        def loss(layer, p, xs):
            return jnp.sum(layer.apply({"params": p}, xs) ** 2)

        @shard_map(mesh=mesh, in_specs=(pspec, P("tp")),
                   out_specs=(P("tp"), pspec))
        def run_sp(p, xs):
            layer = make(True)
            out = layer.apply({"params": p}, xs)
            g = jax.grad(lambda q: loss(layer, q, xs))(p)
            # tp-sharded wgrads are complete per shard; replicated params
            # (router, b2) get identical grads on every rank under SP's
            # full-seq routing, so no extra reduction is needed.
            return out, g

        @shard_map(mesh=mesh, in_specs=(pspec, P()), out_specs=(P(), pspec))
        def run_full(p, xs):
            layer = make(False)
            out = layer.apply({"params": p}, xs)
            g = jax.grad(lambda q: loss(layer, q, xs))(p)
            return out, g

        out_sp, g_sp = run_sp(params, x)
        out_full, g_full = run_full(params, x)
        np.testing.assert_allclose(np.asarray(out_sp), np.asarray(out_full),
                                   rtol=2e-4, atol=2e-4)
        for (pa, ga), (_, gb) in zip(
                jax.tree_util.tree_leaves_with_path(g_sp),
                jax.tree_util.tree_leaves_with_path(g_full)):
            np.testing.assert_allclose(
                np.asarray(ga), np.asarray(gb), rtol=2e-4, atol=2e-4,
                err_msg=str(pa))

    def test_bert_with_moe_layers(self):
        """The BERT family shares ParallelTransformer, so the MoE config
        knobs apply there too."""
        from apex_tpu.models import BertModel, TransformerConfig
        from apex_tpu.transformer.enums import AttnMaskType

        parallel_state.destroy_model_parallel()
        cfg = TransformerConfig(
            hidden_size=32, num_layers=2, num_attention_heads=4,
            vocab_size=64, max_position_embeddings=16,
            compute_dtype=jnp.float32, use_flash_attention=False,
            attn_mask_type=AttnMaskType.padding, num_moe_experts=2)
        model = BertModel(cfg)
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, 64, (2, 16)))
        mask = jnp.ones((2, 16), jnp.int32)
        ttype = jnp.zeros((2, 16), jnp.int32)
        variables = model.init(jax.random.PRNGKey(0), tokens, mask, ttype)
        (mlm, nsp), mut = model.apply(
            {"params": variables["params"]}, tokens, mask, ttype,
            mutable=["moe_losses"])
        assert np.isfinite(np.asarray(mlm)).all()
        assert float(moe_loss_from_variables(mut, 1.0)) > 0


class TestDDPExpertSync:
    """Production DDP sync paths honor the split replica-set rule:
    dense grads average over dp x ep, expert shards over dp alone."""

    def _mesh(self):
        parallel_state.initialize_model_parallel(
            expert_model_parallel_size_=2, devices=jax.devices()[:4])
        return parallel_state.get_mesh()  # dp=2, ep=2

    def _check(self, sync_fn):
        from apex_tpu.parallel.distributed import (
            all_reduce_gradients,
            all_reduce_gradients_bucketed,
        )

        mesh = self._mesh()

        @shard_map(mesh=mesh, in_specs=(), out_specs=(P(), P("ep")))
        def run():
            dpr = jax.lax.axis_index("dp").astype(jnp.float32)
            epr = jax.lax.axis_index("ep").astype(jnp.float32)
            grads = {"dense": (dpr * 2 + epr).reshape(1),
                     "mlp": {"experts": {"w1": (dpr * 10 + epr).reshape(1)}}}
            fn = (all_reduce_gradients_bucketed if sync_fn == "bucketed"
                  else all_reduce_gradients)
            out = fn(grads, axis_name=("dp", "ep"),
                     expert_param_predicate=is_expert_param,
                     expert_axis_name="dp")
            return out["dense"], out["mlp"]["experts"]["w1"]

        dense, expert = run()
        # dense: mean over all 4 cells of dp*2+ep = {0,1,2,3} -> 1.5
        np.testing.assert_allclose(np.asarray(dense), [1.5])
        # expert (per ep rank r): mean over dp of dp*10+r -> 5+r
        np.testing.assert_allclose(np.asarray(expert), [5.0, 6.0])

    def test_per_leaf_sync(self):
        self._check("per_leaf")

    def test_bucketed_sync(self):
        self._check("bucketed")

    def test_ddp_class_sync_and_module_mode_guard(self):
        from apex_tpu.parallel import DistributedDataParallel

        mesh = self._mesh()
        ddp = DistributedDataParallel(
            axis_name=("dp", "ep"), expert_param_predicate=is_expert_param,
            expert_axis_name="dp")

        @shard_map(mesh=mesh, in_specs=(), out_specs=P("ep"))
        def run():
            dpr = jax.lax.axis_index("dp").astype(jnp.float32)
            epr = jax.lax.axis_index("ep").astype(jnp.float32)
            g = ddp.sync({"experts": {"w": (dpr * 10 + epr).reshape(1)}})
            return g["experts"]["w"]

        np.testing.assert_allclose(np.asarray(run()), [5.0, 6.0])
        with pytest.raises(NotImplementedError):
            ddp(lambda p: p)

    def test_moe_under_pp_refused(self):
        """The pipelined harness cannot thread router aux losses across
        stages; MoE configs must be rejected, not silently untrained."""
        from apex_tpu.models.transformer_lm import TransformerConfig
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.transformer.amp.grad_scaler import GradScaler
        from apex_tpu.transformer.testing.gpt_3d import build_gpt_3d_harness

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            pipeline_model_parallel_size_=2, devices=jax.devices()[:2])
        cfg = TransformerConfig(
            hidden_size=32, num_layers=6, num_attention_heads=4,
            vocab_size=64, max_position_embeddings=16,
            use_flash_attention=False, num_moe_experts=2, moe_layer_freq=2)
        with pytest.raises(ValueError, match="gpt_moe"):
            build_gpt_3d_harness(cfg, mesh, FusedAdam(lr=1e-3),
                                 GradScaler(enabled=False), pp=2, seq=16,
                                 microbatch=1, num_microbatches=2)

    def test_aux_loss_drop_warns(self):
        import warnings as w

        from apex_tpu.transformer.moe import layer as moe_layer

        parallel_state.destroy_model_parallel()
        layer = SwitchMLP(hidden_size=16, ffn_hidden_size=32, num_experts=2,
                          compute_dtype=jnp.float32)
        x = jnp.ones((4, 1, 16))
        params = layer.init(jax.random.PRNGKey(0), x)["params"]
        moe_layer._WARNED_DROPPED_LOSSES = False  # once-per-process flag
        with w.catch_warnings(record=True) as caught:
            w.simplefilter("always")
            layer.apply({"params": params}, x)  # no mutable -> warn
        assert any("moe_losses" in str(c.message) for c in caught)
        moe_layer._WARNED_DROPPED_LOSSES = False
        with w.catch_warnings(record=True) as caught:
            w.simplefilter("always")
            layer.apply({"params": params}, x, mutable=["moe_losses"])
        assert not any("moe_losses" in str(c.message) for c in caught)
        # eval opt-out
        quiet = SwitchMLP(hidden_size=16, ffn_hidden_size=32, num_experts=2,
                          compute_dtype=jnp.float32,
                          warn_on_dropped_losses=False)
        with w.catch_warnings(record=True) as caught:
            w.simplefilter("always")
            quiet.apply({"params": params}, x)
        assert not any("moe_losses" in str(c.message) for c in caught)


class TestGPTMoEEndToEnd:
    @pytest.mark.slow
    def test_moe_gpt_ep_training_loss_decreases(self):
        """dp=2 x ep=2 x tp=2 MoE GPT: loss trends down over real steps
        (the ep analog of test_gpt_minimal's 3D run)."""
        from apex_tpu.models.transformer_lm import TransformerConfig
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.transformer.testing.gpt_moe import build_gpt_moe_harness

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=2, expert_model_parallel_size_=2,
            devices=jax.devices()[:8])
        cfg = TransformerConfig(
            hidden_size=32, num_layers=2, num_attention_heads=4,
            vocab_size=64, max_position_embeddings=16,
            compute_dtype=jnp.float32, use_flash_attention=False,
            num_moe_experts=4, moe_capacity_factor=2.0)
        SEQ, B = 16, 8  # dp*ep = 4 cells x 2 per-cell batch
        rng = np.random.RandomState(0)
        data = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(B, SEQ + 1)))
        tokens, labels = data[:, :-1], data[:, 1:]

        opt = FusedAdam(lr=1e-2)
        init_state, step = build_gpt_moe_harness(cfg, mesh, opt)
        params, opt_state = init_state(jax.random.PRNGKey(0), tokens)
        losses = []
        for _ in range(8):
            params, opt_state, loss = step(params, opt_state, tokens, labels)
            losses.append(float(loss))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] * 0.9, losses


class TestMoEWithZeRO:
    def test_distributed_fused_adam_with_expert_params(self):
        """ZeRO (dp-sharded) Adam + expert parallelism: dense grads
        pre-averaged over ep, expert shards left per-cell; resulting
        updates match a hand-computed Adam step per replica set."""
        from apex_tpu.contrib.optimizers import DistributedFusedAdam
        from apex_tpu.parallel.distributed import all_reduce_gradients

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            expert_model_parallel_size_=2, devices=jax.devices()[:4])
        assert mesh.shape["dp"] == 2 and mesh.shape["ep"] == 2
        opt = DistributedFusedAdam(lr=0.1, weight_decay=0.0)

        @shard_map(mesh=mesh, in_specs=(), out_specs=(P(), P("ep")))
        def run():
            dpr = jax.lax.axis_index("dp").astype(jnp.float32)
            epr = jax.lax.axis_index("ep").astype(jnp.float32)
            params = {"dense": jnp.zeros((4,)),
                      "blk": {"experts": {"w": jnp.zeros((4,))}}}
            grads = {"dense": jnp.full((4,), dpr * 2 + epr),
                     "blk": {"experts": {"w": jnp.full((4,), dpr * 10 + epr)}}}
            grads = all_reduce_gradients(
                grads, axis_name="ep", expert_param_predicate=is_expert_param,
                expert_axis_name=())
            opt_state = opt.init(params)
            new_params, _ = opt.step(grads, opt_state, params)
            return new_params["dense"], new_params["blk"]["experts"]["w"][None]

        dense, expert = run()
        # First Adam step moves each param by -lr * sign(grad) (bias
        # correction cancels); all synced grads here are positive.
        np.testing.assert_allclose(np.asarray(dense), -0.1 * np.ones(4),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(expert),
                                   -0.1 * np.ones((2, 4)), rtol=1e-5)

    def test_zero_dense_grads_identical_across_ep(self):
        """After the pre-sync + ZeRO step, dense params remain bitwise
        identical across ep ranks (the divergence the composition rule
        prevents)."""
        from apex_tpu.contrib.optimizers import DistributedFusedAdam
        from apex_tpu.parallel.distributed import all_reduce_gradients

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            expert_model_parallel_size_=2, devices=jax.devices()[:4])
        opt = DistributedFusedAdam(lr=0.05)
        rng = np.random.RandomState(3)
        base = jnp.asarray(rng.randn(8), jnp.float32)

        @shard_map(mesh=mesh, in_specs=P(), out_specs=P("ep"))
        def run(b):
            dpr = jax.lax.axis_index("dp").astype(jnp.float32)
            epr = jax.lax.axis_index("ep").astype(jnp.float32)
            params = {"dense": b, "mlp": {"experts": {"w": b * 0}}}
            grads = {"dense": b * (1 + dpr) * (1 + epr),
                     "mlp": {"experts": {"w": b + dpr + epr}}}
            grads = all_reduce_gradients(
                grads, axis_name="ep", expert_param_predicate=is_expert_param,
                expert_axis_name=())
            state = opt.init(params)
            new_params, _ = opt.step(grads, state, params)
            return new_params["dense"][None]

        per_ep = np.asarray(run(base))  # [ep, 8]
        np.testing.assert_array_equal(per_ep[0], per_ep[1])


class TestMoECheckpoint:
    @pytest.mark.slow
    def test_moe_ep_training_state_roundtrip(self, tmp_path):
        """ep-sharded MoE training state survives save/restore: the
        resumed run reproduces the uninterrupted run's losses exactly."""
        from apex_tpu import checkpoint
        from apex_tpu.models.transformer_lm import TransformerConfig
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.transformer.testing.gpt_moe import build_gpt_moe_harness

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            expert_model_parallel_size_=2, devices=jax.devices()[:2])
        cfg = TransformerConfig(
            hidden_size=32, num_layers=2, num_attention_heads=4,
            vocab_size=64, max_position_embeddings=16,
            compute_dtype=jnp.float32, use_flash_attention=False,
            num_moe_experts=2, moe_capacity_factor=2.0)
        SEQ, B = 16, 4
        rng = np.random.RandomState(0)
        data = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(B, SEQ + 1)))
        tokens, labels = data[:, :-1], data[:, 1:]

        opt = FusedAdam(lr=1e-2)
        init_state, step = build_gpt_moe_harness(cfg, mesh, opt)
        params, opt_state = init_state(jax.random.PRNGKey(0), tokens)
        for _ in range(2):
            params, opt_state, _ = step(params, opt_state, tokens, labels)

        checkpoint.save_training_state(str(tmp_path), 2, params, opt_state)

        ref = []
        p, o = params, opt_state
        for _ in range(2):
            p, o, loss = step(p, o, tokens, labels)
            ref.append(float(loss))

        restored = checkpoint.restore_training_state(str(tmp_path))
        p, o = restored["params"], restored["opt_state"]
        resumed = []
        for _ in range(2):
            p, o, loss = step(p, o, tokens, labels)
            resumed.append(float(loss))
        np.testing.assert_allclose(resumed, ref, rtol=1e-6)


class TestGPTMoE:
    @pytest.mark.slow  # tier-1 budget (round 23): bert_with_moe_layers + ep4_matches_local cover MoE training
    def test_gpt_with_moe_layers_trains(self):
        from apex_tpu.models import GPTModel, TransformerConfig

        parallel_state.destroy_model_parallel()
        cfg = TransformerConfig(
            hidden_size=32, num_layers=2, num_attention_heads=4,
            vocab_size=64, max_position_embeddings=16,
            compute_dtype=jnp.float32, use_flash_attention=False,
            num_moe_experts=4, moe_layer_freq=2)  # layer 0 MoE, layer 1 dense
        model = GPTModel(cfg)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 64, size=(2, 16)))
        variables = model.init(jax.random.PRNGKey(0), tokens)
        flat = jax.tree_util.tree_leaves_with_path(variables["params"])
        paths = ["/".join(str(k.key) for k in p) for p, _ in flat]
        assert any("layer_0/mlp/experts" in p for p in paths)
        assert any("layer_1/mlp/dense_h_to_4h" in p for p in paths)

        from apex_tpu.models.gpt import gpt_loss_fn

        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p}, tokens, mutable=["moe_losses"])
            labels = jnp.roll(tokens, -1, axis=-1)
            return gpt_loss_fn(logits, labels) + moe_loss_from_variables(
                mut, cfg.moe_aux_loss_coeff, cfg.moe_z_loss_coeff)

        loss, g = jax.value_and_grad(loss_fn)(variables["params"])
        assert np.isfinite(float(loss))
        router_g = g["transformer"]["layer_0"]["mlp"]["router"]["gate_weight"]
        assert float(jnp.abs(router_g).sum()) > 0


class TestMoEPipelineParallel:
    """Round-2: MoE composes with pipeline parallelism (uniform stack).
    Round 1 refused this; the schedule's aux_loss contract now backprops
    each stage's router losses from its own backward unit."""

    def _run(self, aux_coeff, steps=6):
        from apex_tpu.models.transformer_lm import TransformerConfig
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.transformer.amp.grad_scaler import GradScaler
        from apex_tpu.transformer.testing.gpt_3d import build_gpt_3d_harness

        PP_, DP_, TP_ = 2, 2, 2
        SEQ_, MB_, M_ = 16, 2, 2
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=TP_,
            pipeline_model_parallel_size_=PP_, devices=jax.devices()[:8])
        cfg = TransformerConfig(
            hidden_size=64, num_layers=2 * PP_, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=32,
            compute_dtype=jnp.bfloat16, sequence_parallel=True,
            use_flash_attention=False, num_moe_experts=2,
            moe_layer_freq=1, moe_capacity_factor=2.0,
            moe_aux_loss_coeff=aux_coeff)
        global_b = MB_ * M_ * DP_
        rng = np.random.RandomState(0)
        base = rng.randint(0, 32, size=(global_b, 1))
        tokens = jnp.asarray((base + np.arange(SEQ_)) % 32)
        labels = jnp.asarray((base + np.arange(1, SEQ_ + 1)) % 32)
        opt = FusedAdam(lr=5e-3, master_weights=True)
        scaler = GradScaler(enabled=True)
        init_state, step = build_gpt_3d_harness(
            cfg, mesh, opt, scaler, pp=PP_, seq=SEQ_, microbatch=MB_,
            num_microbatches=M_)
        state = init_state(jax.random.PRNGKey(0), tokens, labels)
        losses = []
        for _ in range(steps):
            *state, loss = step(*state, tokens, labels)
            losses.append(float(np.asarray(loss).sum()) / DP_ / M_)
        parallel_state.destroy_model_parallel()
        return losses, state[0]

    def test_moe_pp_training_loss_decreases(self):
        losses, _ = self._run(aux_coeff=1e-2, steps=10)
        assert np.isfinite(losses).all()
        assert losses[-1] < 0.8 * losses[0], losses

    def test_router_aux_grads_reach_first_stage(self):
        """The aux coefficient must change the FIRST pipeline stage's
        router update — proof the per-stage aux cotangent flows (with
        last-stage-only loss it could only reach stage P-1)."""
        _, params_a = self._run(aux_coeff=0.0, steps=1)
        _, params_b = self._run(aux_coeff=10.0, steps=1)

        def router_leaf(params):
            flat = jax.tree_util.tree_flatten_with_path(params)[0]
            leaves = [v for k, v in flat if "router" in jax.tree_util.keystr(k)]
            assert leaves, [jax.tree_util.keystr(k) for k, _ in flat][:8]
            return np.asarray(leaves[0])  # [pp, ...] stacked rows

        ra, rb = router_leaf(params_a), router_leaf(params_b)
        # first pipeline stage's router row differs between coefficients
        assert not np.allclose(ra[0], rb[0], atol=1e-7), \
            "aux loss did not reach the first stage's router"

    def test_refuses_expert_parallel_mesh(self):
        from apex_tpu.models.transformer_lm import TransformerConfig
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.transformer.amp.grad_scaler import GradScaler
        from apex_tpu.transformer.testing.gpt_3d import build_gpt_3d_harness

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            pipeline_model_parallel_size_=2, expert_model_parallel_size_=2,
            devices=jax.devices()[:8])
        cfg = TransformerConfig(
            hidden_size=64, num_layers=4, num_attention_heads=4,
            vocab_size=128, max_position_embeddings=32,
            num_moe_experts=2, moe_layer_freq=1)
        with pytest.raises(ValueError, match="expert parallelism"):
            build_gpt_3d_harness(cfg, mesh, FusedAdam(lr=1e-3),
                                 GradScaler(enabled=False), pp=2, seq=16,
                                 microbatch=2, num_microbatches=2)
