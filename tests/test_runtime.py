"""Runtime substrate: optimizer, checkpointing, pipeline, approx collectives."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.approx_comm import (LEVELS, _quant_roundtrip,
                                    characterize_fidelity, compressed_mean,
                                    make_grad_compressor)
from repro.data.pipeline import BackupFetcher, Prefetcher, TokenStream
from repro.launch.mesh import make_mesh
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state


class TestAdamW:
    def test_quadratic_converges(self):
        cfg = AdamWConfig(learning_rate=0.1, weight_decay=0.0, warmup_steps=1)
        params = {"w": jnp.asarray([3.0, -2.0])}
        state = init_opt_state(params)
        loss = lambda p: jnp.sum(p["w"] ** 2)
        for _ in range(150):
            g = jax.grad(loss)(params)
            params, state = adamw_update(cfg, params, g, state)
        assert float(loss(params)) < 1e-3

    def test_grad_clip_bounds_update(self):
        cfg = AdamWConfig(learning_rate=1.0, grad_clip=1e-3, warmup_steps=1,
                          weight_decay=0.0)
        params = {"w": jnp.zeros(4)}
        state = init_opt_state(params)
        huge = {"w": jnp.full(4, 1e9)}
        new, state = adamw_update(cfg, params, huge, state)
        assert float(jnp.abs(new["w"]).max()) < 2.0   # step ~ lr * mhat/sqrt(vhat)

    def test_weight_decay_on_matrices_only(self):
        cfg = AdamWConfig(learning_rate=0.01, weight_decay=0.5, warmup_steps=1)
        params = {"mat": jnp.ones((4, 4)), "vec": jnp.ones(4)}
        state = init_opt_state(params)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        new, _ = adamw_update(cfg, params, zeros, state)
        assert float(new["mat"][0, 0]) < 1.0
        np.testing.assert_allclose(np.asarray(new["vec"]), 1.0)


class TestCheckpointer:
    def _tree(self, x=0.0):
        return {"a": {"w": jnp.full((8, 8), 1.0 + x)},
                "b": jnp.arange(16, dtype=jnp.float32) + x}

    def test_roundtrip(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(5, self._tree(1.0), meta={"loss": 3.0})
        restored, step = ck.restore(self._tree())
        assert step == 5
        np.testing.assert_allclose(np.asarray(restored["a"]["w"]), 2.0)

    def test_corruption_falls_back_to_previous(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(1, self._tree(1.0))
        ck.save(2, self._tree(2.0))
        ck.corrupt(2)
        assert ck.latest_valid_step() == 1
        restored, step = ck.restore(self._tree())
        assert step == 1
        np.testing.assert_allclose(np.asarray(restored["b"])[0], 1.0)

    def test_gc_keeps_last_k(self, tmp_path):
        ck = Checkpointer(str(tmp_path), keep=2)
        for s in range(5):
            ck.save(s, self._tree(float(s)))
        assert ck.steps() == [3, 4]

    def test_async_save(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        t = ck.save_async(7, self._tree(7.0))
        t.join(timeout=30)
        assert ck.latest_valid_step() == 7

    def test_elastic_restore_with_shardings(self, tmp_path):
        """Restore onto an explicit (1-device) mesh sharding -- the elastic
        path: stored arrays are unsharded, any mesh works."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        ck = Checkpointer(str(tmp_path))
        ck.save(3, self._tree(3.0))
        mesh = make_mesh((1, 1), ("data", "model"))
        sh = {"a": {"w": NamedSharding(mesh, P("data", "model"))},
              "b": NamedSharding(mesh, P(None))}
        restored, _ = ck.restore(self._tree(), shardings=sh)
        assert restored["a"]["w"].sharding.mesh.shape["data"] == 1


class TestPipeline:
    def test_token_stream_deterministic(self):
        a = TokenStream(512, 2, 32, seed=3).next_batch()
        b = TokenStream(512, 2, 32, seed=3).next_batch()
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        # next-token alignment
        s = TokenStream(512, 1, 16, seed=0)
        batch = s.next_batch()
        np.testing.assert_array_equal(batch["tokens"][0, 1:],
                                      batch["labels"][0, :-1])

    def test_prefetcher_order_and_completion(self):
        pf = Prefetcher(iter(range(10)), depth=3)
        assert list(pf) == list(range(10))

    def test_prefetcher_propagates_errors(self):
        def gen():
            yield 1
            raise RuntimeError("boom")
        pf = Prefetcher(gen(), depth=2)
        assert next(pf) == 1
        with pytest.raises(RuntimeError, match="boom"):
            next(pf)

    def test_backup_fetcher_hedges_stragglers(self):
        calls = {"n": 0}

        def fetch(i):
            calls["n"] += 1
            # every 5th fetch is a straggler
            if i % 5 == 4 and calls["n"] <= 20:
                time.sleep(0.25)
            else:
                time.sleep(0.005)
            return i

        bf = BackupFetcher(fetch, hedge_factor=3.0, min_history=4)
        out = [bf.fetch(i) for i in range(15)]
        assert out == list(range(15))
        assert bf.hedges_issued >= 1


class TestApproxComm:
    def test_roundtrip_error_small(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (512, 512))
        for bits, tol in ((8, 0.01), (4, 0.15)):
            rt = _quant_roundtrip(x, bits)
            rel = float(jnp.abs(rt - x).max() / jnp.abs(x).max())
            assert rel < tol, (bits, rel)

    def test_fidelity_table_monotone(self):
        g = {"w": jax.random.normal(jax.random.PRNGKey(1), (256, 512))}
        fid = characterize_fidelity(g)
        assert fid[16] == 1.0
        assert fid[16] >= fid[8] >= fid[4] > 0.95

    def test_compressed_mean_matches_pmean(self):
        from jax.sharding import PartitionSpec as P
        mesh = make_mesh((1,), ("pod",))
        x = jax.random.normal(jax.random.PRNGKey(2), (256, 512))

        f = jax.shard_map(lambda v: compressed_mean(v, "pod", 8),
                          mesh=mesh, in_specs=P(), out_specs=P(),
                          check_vma=False)
        out = f(x)
        exact = x  # single member mean = itself (up to quantization)
        assert float(jnp.abs(out - exact).max() /
                     jnp.abs(exact).max()) < 0.01

    def test_grad_compressor_hook(self):
        grads = {"big": jnp.ones((512, 512)) * 0.37,
                 "small": jnp.ones((4,)) * 0.37}
        hook = make_grad_compressor(8, min_size=1024)
        out = hook(grads)
        # small leaves untouched; big leaves quantized (value changes slightly)
        np.testing.assert_array_equal(np.asarray(out["small"]),
                                      np.asarray(grads["small"]))
        assert np.abs(np.asarray(out["big"]) - 0.37).max() < 0.37 / 127

    def test_collective_controller_closed_loop(self):
        """ROADMAP PR 4 follow-up: the compression level is driven by the
        JITTED controller (one-lane ``fleet_controller_step`` on the
        shared ``ControllerParams`` path) -- decisions bit-identical to the
        host PI controller, levels drop under link contention and recover
        after, the fidelity floor governs every feasible decision, and the
        whole run compiles exactly once."""
        from repro.core.approx_comm import (CollectiveController,
                                            collective_bytes_for,
                                            fidelity_table)
        from repro.core.characterization import LatencyRegression
        from repro.core.controller import (ControllerConfig,
                                           LatencyController)
        grad_bytes = 4e6
        fidelity = {16: 1.0, 8: 0.999, 4: 0.985}
        bw = 3e9
        target = 1.5 * grad_bytes / bw
        ctl = CollectiveController(grad_bytes, fidelity,
                                   latency_target=target,
                                   fidelity_floor=0.98, slope=1.0 / bw)
        host = LatencyController(
            ControllerConfig(target, 0.98, error_threshold=0.05 * target),
            fidelity_table(grad_bytes, fidelity),
            LatencyRegression(slope=1.0 / bw, intercept=1e-4))
        bits, used = 16, []
        for step in range(60):
            contention = 8.0 if 20 <= step < 40 else 1.0
            lat = (collective_bytes_for(grad_bytes, bits)
                   / (bw / contention) + 1e-4)
            d = ctl.update(lat)
            dh = host.update(lat)
            assert d.setting_index == dh.setting_index, step
            assert d.acted == dh.acted, step
            assert d.feasible == dh.feasible, step
            if d.feasible and d.setting_index >= 0:
                assert fidelity[d.bits] >= 0.98
            bits = d.bits
            used.append(bits)
        assert min(used[20:40]) < 16       # compressed under contention
        assert used[-1] == 16              # relaxed back to exact transport
        assert ctl.cache_size() == 1
