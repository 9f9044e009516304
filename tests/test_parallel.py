"""Multi-chip sharding tests on the virtual 8-device CPU mesh:
pipeline-vs-dense equivalence, sharded train step, mesh factorization,
graft entry points."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ome_tpu.models import config as cfgs
from ome_tpu.models import llama
from ome_tpu.parallel import pipeline, sharding
from ome_tpu.parallel.mesh import AXES, MeshConfig, build_mesh
from ome_tpu.train import step as train_step_lib


@pytest.fixture(scope="module")
def mesh8():
    return build_mesh(MeshConfig(dp=2, pp=2, tp=2))


class TestMeshConfig:
    def test_auto_factorization(self):
        m = MeshConfig.auto(8, num_layers=4)
        assert m.size == 8 and m.pp == 2 and m.tp == 2 and m.dp == 2
        assert MeshConfig.auto(1).size == 1
        assert MeshConfig.auto(2).size == 2
        assert MeshConfig.auto(4, num_layers=4).size == 4
        assert MeshConfig.auto(16, num_layers=4).size == 16

    def test_build_mesh_axes(self, mesh8):
        assert mesh8.axis_names == AXES
        assert mesh8.devices.shape == (2, 2, 2)


class TestShardingRules:
    def test_param_specs_cover_all_leaves(self):
        cfg = cfgs.tiny_test(moe=True)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        specs = sharding.param_specs(params)
        jax.tree.map(lambda p, s: None, params,
                     jax.tree.map(lambda s: s, specs,
                                  is_leaf=lambda x: isinstance(x, P)))

    def test_shard_params_distributes(self, mesh8):
        cfg = cfgs.tiny_test()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        staged = sharding.stack_to_stages(params, 2)
        shp = sharding.shard_params(staged, mesh8, pipeline=True)
        wq = shp["layers"]["wq"]  # [pp, l, H, Dh, D], pp+tp sharded
        n_shards = len({s.device for s in wq.addressable_shards})
        assert n_shards == 8  # spread over all devices (dp replicates)
        shard_shape = wq.addressable_shards[0].data.shape
        assert shard_shape[0] == 1  # pp split
        assert shard_shape[2] == cfg.num_heads // 2  # tp split on heads
        assert shard_shape[3:] == (cfg.head_dim, cfg.hidden_size)

    def test_stack_unstack_roundtrip(self):
        cfg = cfgs.tiny_test()
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        staged = sharding.stack_to_stages(params, 2)
        assert staged["layers"]["wq"].shape[0] == 2
        back = sharding.unstack_stages(staged)
        assert jnp.array_equal(back["layers"]["wq"], params["layers"]["wq"])


class TestPipelineEquivalence:
    def test_pipeline_matches_dense_forward(self, mesh8):
        """pp-staged sharded forward == plain single-device forward."""
        cfg = cfgs.tiny_test().replace(dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                    cfg.vocab_size)
        ref_logits, _ = llama.forward(params, cfg, tokens)

        staged = sharding.stack_to_stages(params, 2)
        staged = sharding.shard_params(staged, mesh8, pipeline=True)
        with jax.set_mesh(mesh8):
            out = jax.jit(lambda p, t: pipeline.pipeline_forward(
                p, cfg, t, pp=2, num_microbatches=2, mesh=mesh8))(staged, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_logits),
                                   atol=2e-4, rtol=2e-4)

    def test_pipeline_moe_matches_dense(self, mesh8):
        cfg = cfgs.tiny_test(moe=True).replace(dtype=jnp.float32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0,
                                    cfg.vocab_size)
        ref_logits, _ = llama.forward(params, cfg, tokens)
        staged = sharding.stack_to_stages(params, 2)
        staged = sharding.shard_params(staged, mesh8, pipeline=True)
        with jax.set_mesh(mesh8):
            out = jax.jit(lambda p, t: pipeline.pipeline_forward(
                p, cfg, t, pp=2, num_microbatches=4, mesh=mesh8))(staged, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_logits),
                                   atol=2e-4, rtol=2e-4)


    def test_pipeline_gemma2_matches_dense(self, mesh8):
        """The gemma2 block shape (alternating sliding/global windows,
        GeGLU, post-block (1+w) norms, softcaps, scaled embeddings)
        rides the pipeline via the per-stage layer-pair scan (round-2
        review weak #6 lifted)."""
        cfg = cfgs.tiny_test().replace(
            dtype=jnp.float32, alt_sliding_window=True, sliding_window=8,
            mlp_activation="gelu_tanh", post_block_norms=True,
            embed_scale=True, unit_offset_norm=True,
            attn_logit_softcap=50.0, final_logit_softcap=30.0,
            query_scale=16 ** -0.5)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0,
                                    cfg.vocab_size)
        ref_logits, _ = llama.forward(params, cfg, tokens)
        staged = sharding.stack_to_stages(params, 2)
        staged = sharding.shard_params(staged, mesh8, pipeline=True)
        with jax.set_mesh(mesh8):
            out = jax.jit(lambda p, t: pipeline.pipeline_forward(
                p, cfg, t, pp=2, num_microbatches=2, mesh=mesh8))(staged,
                                                                  tokens)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref_logits),
                                   atol=2e-4, rtol=2e-4)

    def test_pipeline_gemma2_odd_stage_depth_refused(self, mesh8):
        cfg = cfgs.tiny_test().replace(alt_sliding_window=True,
                                       num_layers=4)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ValueError, match="even layer count"):
            pipeline.pipeline_forward(
                params, cfg, jnp.zeros((2, 8), jnp.int32), pp=4,
                num_microbatches=2)


class TestTrainStep:
    def test_sharded_train_step_loss_decreases(self, mesh8):
        cfg = cfgs.tiny_test(moe=True)
        mesh_cfg = MeshConfig(dp=2, pp=2, tp=2)
        train_step, init_state = train_step_lib.make_train_step(
            cfg, mesh8, mesh_cfg, num_microbatches=4, lr=1e-2)
        with jax.set_mesh(mesh8):
            params, opt_state = init_state(jax.random.PRNGKey(0))
            tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                        cfg.vocab_size)
            targets = jnp.full_like(tokens, 7)  # constant target: fast to fit
            sh = train_step_lib.data_sharding(mesh8)
            tokens, targets = jax.device_put((tokens, targets), sh)
            losses = []
            for _ in range(6):
                params, opt_state, loss = train_step(params, opt_state,
                                                     tokens, targets)
                losses.append(float(loss))
        assert losses[-1] < losses[0] - 1.0  # must drop sharply on constant


class TestGraftEntry:
    def test_entry_compiles(self):
        import __graft_entry__ as g
        fn, args = g.entry()
        logits = jax.jit(fn)(*args)
        assert logits.shape[0] == args[1].shape[0]

    def test_dryrun_multichip_8(self, capsys):
        import __graft_entry__ as g
        g.dryrun_multichip(8)
        assert "mesh=(dp=2, pp=2, tp=2)" in capsys.readouterr().out
