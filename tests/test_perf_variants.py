"""§Perf variant correctness: sharding constraints and remat policies must
not change the math (subprocess mesh tests), and the ring-buffer prefill
(the long_500k sliding-window path) must agree with windowed attention."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models.model import Model

from tests.test_distributed import run_with_devices


class TestVariantNumericalEquivalence:
    def test_attn_sharding_constraints_preserve_outputs(self):
        out = run_with_devices("""
            import jax, jax.numpy as jnp, numpy as np, dataclasses
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.configs import get_arch
            from repro.models.model import Model
            from repro.launch.sharding import param_pspecs
            cfg = dataclasses.replace(get_arch("yi-6b").reduced(),
                                      vocab_pad_multiple=64)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 2), ("data", "model"))
            tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                        cfg.vocab)
            outs = {}
            for opt in (False, True):
                model = Model(cfg, mesh=mesh, opt_attn_sharding=opt,
                              opt_seq_parallel=opt)
                params = model.init_params(jax.random.PRNGKey(0))
                pspec = param_pspecs(cfg, ("data",))
                named = jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), pspec,
                    is_leaf=lambda x: isinstance(x, P))
                params = jax.device_put(params, named)
                logits, _ = jax.jit(lambda p, t: model.forward(p, t))(
                    params, tokens)
                outs[opt] = np.asarray(logits)
            np.testing.assert_allclose(outs[False], outs[True],
                                       rtol=2e-4, atol=2e-4)
            print("VARIANT-EQ-OK")
        """, n_devices=4)
        assert "VARIANT-EQ-OK" in out


class TestRingPrefill:
    """cache_size < seq_len: the sliding-window ring prefill (long_500k
    substrate) must hand decode a cache equivalent to windowed attention."""

    @pytest.mark.parametrize("arch", ["yi-6b", "musicgen-large"])
    def test_ring_prefill_decode_matches_windowed_forward(self, arch):
        import dataclasses
        window = 16
        cfg = dataclasses.replace(get_arch(arch).reduced(),
                                  sliding_window=window, n_prefix=0)
        m = Model(cfg)
        params = m.init_params(jax.random.PRNGKey(0))
        L = 40
        if cfg.n_codebooks > 1:
            tokens = jax.random.randint(jax.random.PRNGKey(1),
                                        (2, L, cfg.n_codebooks), 0, cfg.vocab)
        else:
            tokens = jax.random.randint(jax.random.PRNGKey(1), (2, L), 0,
                                        cfg.vocab)
        # ring cache smaller than the sequence: only the last `window`
        # positions survive — exactly the long_500k memory model
        lg, caches, _ = m.forward(params, tokens, collect_cache=True,
                                  cache_size=window)
        assert caches.kv.size == window
        nt = jnp.argmax(lg[:, -1:], axis=-1)
        dl, _ = m.decode_step(params, caches, nt)
        ext = jnp.concatenate([tokens, nt], axis=1)
        lg2, _ = m.forward(params, ext)
        err = float(jnp.max(jnp.abs(dl[:, 0] - lg2[:, -1])))
        assert err < 5e-3, f"{arch}: ring-prefill decode divergence {err}"

    def test_ring_slot_positions(self):
        cfg = get_arch("yi-6b").reduced()
        import dataclasses
        cfg = dataclasses.replace(cfg, sliding_window=8)
        m = Model(cfg)
        params = m.init_params(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 20), 0,
                                    cfg.vocab)
        _, caches, _ = m.forward(params, tokens, collect_cache=True,
                                 cache_size=8)
        sp = np.asarray(caches.kv.slot_pos)
        # slots hold positions 12..19 at ring indices pos % 8
        assert sorted(sp.tolist()) == list(range(12, 20))
        for i, p in enumerate(sp):
            assert p % 8 == i
