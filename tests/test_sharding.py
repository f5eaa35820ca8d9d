"""Sharding resolver rules: divisibility fallbacks, megatron roles, cache
and batch specs. Pure metadata tests -- no multi-device needed."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get
from repro.launch.sharding import (_param_pspec, batch_shardings,
                                   cache_shardings, param_shardings)
from repro.models import build_model


class FakeEntry:
    def __init__(self, key):
        self.key = key


def spec_of(name, shape, mp=16, stacked=False):
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32)
    return _param_pspec((FakeEntry(name),), leaf, mp, stacked)


class TestParamRules:
    def test_column_parallel(self):
        assert spec_of("wq", (4096, 2048)) == P(None, "model")

    def test_row_parallel(self):
        assert spec_of("wo", (2048, 4096)) == P("model", None)

    def test_divisibility_fallback(self):
        # output dim 75 not divisible by 16 -> replicate
        assert spec_of("wq", (128, 75)) == P(None, None)

    def test_embedding_vocab_sharded(self):
        assert spec_of("table", (152064, 2560)) == P("model", None)

    def test_moe_expert_ff_sharded(self):
        assert spec_of("w_in", (40, 1536, 512)) == P(None, None, "model")
        assert spec_of("w_out", (40, 512, 1536)) == P(None, "model", None)

    def test_stacked_leading_layer_axis(self):
        # (L, d, out): leading scan axis never sharded
        assert spec_of("wq", (36, 2560, 4096), stacked=True) == \
            P(None, None, "model")

    def test_norms_replicated(self):
        assert spec_of("ln1", (2560,)) == P(None)


class TestTreeShardings:
    @pytest.mark.parametrize("arch", ["qwen3_4b", "granite_moe_3b_a800m",
                                      "mamba2_130m", "whisper_medium"])
    def test_param_shardings_cover_tree(self, arch):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        model = build_model(get(arch))
        specs = model.param_specs()
        sh = param_shardings(mesh, specs)
        assert jax.tree.structure(sh) == jax.tree.structure(specs)

    def test_cache_seq_sharded_on_model(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        model = build_model(get("qwen3_4b"))
        cache = model.init_cache_specs(128, 32768)
        sh = cache_shardings(mesh, cache)
        k_spec = sh["main"]["k"].spec
        assert k_spec[2] == "model"        # sequence axis (flash-decoding)

    def test_batch_replicates_when_indivisible(self):
        # B=1 (long_500k) cannot shard over the data axis -> replicate.
        # AbstractMesh: sharding metadata without needing 2 real devices.
        mesh = jax.sharding.AbstractMesh((2, 1), ("data", "model"))
        sh = batch_shardings(
            mesh, {"tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32)})
        assert sh["tokens"].spec == P(None, None)
