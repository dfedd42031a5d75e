"""Model tests: MNIST MLP learns from reader-fed batches (the end-to-end
"aha" slice), transformer LM trains under dp/tp and dp/sp/tp/ep shardings,
ring vs local attention produce the same logits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow    # kernels / model training: minutes-scale (fast lane skips)

jax.config.update('jax_default_matmul_precision', 'highest')


@pytest.fixture(scope='module')
def cpus():
    devices = jax.devices('cpu')
    if len(devices) < 8:
        pytest.skip('needs 8 CPU devices')
    return devices


class TestMnistMlp:
    def test_learns_synthetic_separable(self, cpus):
        from petastorm_tpu.models import mnist_mlp
        rng = np.random.default_rng(0)
        n = 512
        labels = rng.integers(0, 10, n)
        images = rng.standard_normal((n, 784)).astype(np.float32) * 0.05
        images[np.arange(n), labels] += 3.0     # linearly separable signal
        with jax.default_device(cpus[0]):
            params = mnist_mlp.init(jax.random.PRNGKey(0))
            x, y = jnp.asarray(images), jnp.asarray(labels)
            for _ in range(60):
                params, loss = mnist_mlp.train_step(params, x, y, 1e-2)
            acc = float(mnist_mlp.accuracy(params, x, y))
        assert acc > 0.9, acc

    def test_end_to_end_from_reader(self, tmp_path, cpus):
        """parquet -> make_reader -> JaxDataLoader -> train step."""
        from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
        from petastorm_tpu.etl.dataset_metadata import materialize_dataset
        from petastorm_tpu.jax_utils import JaxDataLoader
        from petastorm_tpu.models import mnist_mlp
        from petastorm_tpu.reader import make_reader
        from petastorm_tpu.unischema import Unischema, UnischemaField

        schema = Unischema('Digits', [
            UnischemaField('image', np.float32, (784,), NdarrayCodec(), False),
            UnischemaField('label', np.int64, (), ScalarCodec(), False),
        ])
        rng = np.random.default_rng(0)
        labels = rng.integers(0, 10, 256)
        images = rng.standard_normal((256, 784)).astype(np.float32) * 0.05
        images[np.arange(256), labels] += 3.0
        url = 'file://' + str(tmp_path / 'digits')
        with materialize_dataset(url, schema, rows_per_file=64) as w:
            w.write_rows({'image': images[i], 'label': np.int64(labels[i])}
                         for i in range(256))

        with jax.default_device(cpus[0]):
            params = mnist_mlp.init(jax.random.PRNGKey(0))
            losses = []
            for _ in range(4):  # 4 epochs
                with make_reader(url, reader_pool_type='dummy', num_epochs=1,
                                 seed=0) as reader:
                    loader = JaxDataLoader(reader, batch_size=64)
                    for batch in loader:
                        params, loss = mnist_mlp.train_step(
                            params, jnp.asarray(batch['image']),
                            jnp.asarray(batch['label']), 1e-2)
                        losses.append(float(loss))
        assert losses[-1] < losses[0]


def _tiny_config(**kw):
    from petastorm_tpu.models.transformer_lm import TransformerConfig
    defaults = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                    d_ff=64, max_seq_len=32, dtype=jnp.float32)
    defaults.update(kw)
    return TransformerConfig(**defaults)


class TestTransformerLm:
    def test_forward_shapes_and_causality(self, cpus):
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = _tiny_config()
        with jax.default_device(cpus[0]):
            params = tlm.init(jax.random.PRNGKey(0), cfg)
            toks = jnp.asarray(np.arange(32)[None, :] % 64, jnp.int32)
            logits = tlm.forward(params, toks, cfg)
            assert logits.shape == (1, 32, 64)
            # causality: changing a future token must not affect past logits
            toks2 = toks.at[0, 20].set(5)
            logits2 = tlm.forward(params, toks2, cfg)
        np.testing.assert_allclose(np.asarray(logits[0, :20]),
                                   np.asarray(logits2[0, :20]), atol=1e-5)

    def test_train_step_dense_dp_tp(self, cpus):
        from jax.sharding import NamedSharding, PartitionSpec
        from petastorm_tpu.models import transformer_lm as tlm
        from petastorm_tpu.parallel import make_mesh

        cfg = _tiny_config()
        mesh = make_mesh({'data': 2, 'model': 4}, devices=cpus)
        with jax.default_device(cpus[0]):
            params = tlm.init(jax.random.PRNGKey(0), cfg)
        pshard = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), tlm.param_specs(cfg, mesh),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        params = jax.tree_util.tree_map(jax.device_put, params, pshard)
        optimizer, step = tlm.make_train_step(cfg, mesh)
        opt_state = optimizer.init(params)
        rng = np.random.default_rng(0)
        bshard = NamedSharding(mesh, tlm.batch_spec(mesh))
        toks = jax.device_put(jnp.asarray(rng.integers(0, 64, (4, 32)),
                                          jnp.int32), bshard)
        tgts = jnp.roll(toks, -1, axis=1)
        losses = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, toks, tgts)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_ring_matches_local_attention(self, cpus):
        """Same params, same tokens: ring-attention forward over a seq-sharded
        mesh equals the local blockwise forward."""
        from petastorm_tpu.models import transformer_lm as tlm
        from petastorm_tpu.parallel import make_mesh

        cfg_local = _tiny_config()
        cfg_ring = _tiny_config(attention='ring')
        mesh = make_mesh({'data': 2, 'seq': 4}, devices=cpus)
        with jax.default_device(cpus[0]):
            params = tlm.init(jax.random.PRNGKey(0), cfg_local)
            toks = jnp.asarray(
                np.random.default_rng(0).integers(0, 64, (2, 32)), jnp.int32)
            ref = tlm.forward(params, toks, cfg_local)
        out = tlm.forward(params, toks, cfg_ring, mesh=mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)

    def test_train_step_moe_ring_full_mesh(self, cpus):
        from jax.sharding import NamedSharding, PartitionSpec
        from petastorm_tpu.models import transformer_lm as tlm
        from petastorm_tpu.parallel import make_mesh

        cfg = _tiny_config(n_experts=2, attention='ring')
        mesh = make_mesh({'data': 2, 'seq': 2, 'model': 2}, devices=cpus[:8])
        with jax.default_device(cpus[0]):
            params = tlm.init(jax.random.PRNGKey(1), cfg)
        pshard = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), tlm.param_specs(cfg, mesh),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        params = jax.tree_util.tree_map(jax.device_put, params, pshard)
        optimizer, step = tlm.make_train_step(cfg, mesh)
        opt_state = optimizer.init(params)
        rng = np.random.default_rng(0)
        bshard = NamedSharding(mesh, tlm.batch_spec(mesh))
        toks = jax.device_put(jnp.asarray(rng.integers(0, 64, (4, 32)),
                                          jnp.int32), bshard)
        params, opt_state, loss = step(params, opt_state, toks,
                                       jnp.roll(toks, -1, axis=1))
        assert np.isfinite(float(loss))

    # grad_rtol bounds the worst leaf's gradient gap over the leaf's norm.
    # In bf16 both sides round the same float32 softmax gradient to bf16, and
    # float32 rounding order can move one value by one bf16 step (2**-8).
    @pytest.mark.parametrize('kw,axes,packed,grad_rtol', [
        ({}, None, False, 1e-6),
        ({'dtype': jnp.bfloat16}, None, False, 2.0 ** -8),
        ({}, None, True, 1e-6),
        ({'n_experts': 2}, None, False, 1e-6),
        ({}, {'data': 2, 'model': 2}, False, 1e-6),
    ], ids=['f32', 'bf16', 'packed', 'moe_aux', 'dp2_tp2'])
    def test_loss_matches_log_softmax(self, cpus, kw, axes, packed,
                                      grad_rtol):
        """loss_fn's value and gradient equal the plain float32
        ``-take_along_axis(log_softmax(logits))`` over forward's logits, on
        the same devices (on the mesh, with the vocab axis sharded)."""
        from jax.sharding import NamedSharding, PartitionSpec
        from petastorm_tpu.models import transformer_lm as tlm
        from petastorm_tpu.packing import pack_documents, packed_lm_targets
        from petastorm_tpu.parallel import make_mesh

        cfg = _tiny_config(vocab_size=256, **kw)
        rng = np.random.default_rng(7)
        extra = {}
        if packed:
            batch = pack_documents(
                [list(rng.integers(0, 256, n)) for n in (7, 5, 9, 11)],
                seq_len=32, num_rows=2)
            # the packer returns host arrays; put them on the device here
            toks = jnp.asarray(batch.tokens)
            seg = jnp.asarray(batch.segment_ids)
            tgts, weights = packed_lm_targets(toks, seg)
            extra = dict(positions=jnp.asarray(batch.positions),
                         segment_ids=seg, weights=weights)
        else:
            toks = jnp.asarray(rng.integers(0, 256, (4, 32)), jnp.int32)
            tgts = jnp.roll(toks, -1, axis=1)
        with jax.default_device(cpus[0]):
            params = tlm.init(jax.random.PRNGKey(3), cfg)

        mesh = None
        if axes is not None:
            mesh = make_mesh(axes, devices=cpus[:4])
            pshard = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), tlm.param_specs(cfg, mesh),
                is_leaf=lambda x: isinstance(x, PartitionSpec))
            params = jax.tree_util.tree_map(jax.device_put, params, pshard)
            bshard = NamedSharding(mesh, tlm.batch_spec(mesh))
            toks, tgts = (jax.device_put(a, bshard) for a in (toks, tgts))
            assert params['unembed'].sharding.spec == PartitionSpec(None,
                                                                    'model')

        def reference(params, toks, tgts):
            logits, aux = tlm.forward(
                params, toks, cfg, positions=extra.get('positions'),
                mesh=mesh, return_aux=True,
                segment_ids=extra.get('segment_ids'))
            assert logits.dtype == jnp.float32
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, tgts[..., None], axis=-1)[..., 0]
            if packed:
                w = extra['weights']
                loss = jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
            else:
                loss = jnp.mean(nll)
            return loss + (cfg.moe_aux_weight * aux if cfg.n_experts else 0.0)

        want, want_grads = jax.jit(jax.value_and_grad(reference))(
            params, toks, tgts)
        got, grads = jax.jit(jax.value_and_grad(
            lambda p, x, y: tlm.loss_fn(p, x, y, cfg, mesh, **extra)))(
                params, toks, tgts)

        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
        gaps = jax.tree_util.tree_map(
            lambda a, b: float(np.linalg.norm(np.asarray(a, np.float64)
                                              - np.asarray(b, np.float64))
                               / np.linalg.norm(np.asarray(b, np.float64))),
            grads, want_grads)
        assert max(jax.tree_util.tree_leaves(gaps)) <= grad_rtol, gaps


class TestGenerate:
    @pytest.mark.parametrize('kw', [
        {},                                              # dense MHA
        {'n_kv_heads': 2},                               # GQA cache
        {'n_experts': 4, 'moe_top_k': 2,
         'moe_capacity_factor': 4.0},                    # MoE (no drops)
    ])
    def test_greedy_matches_teacher_forced_forward(self, cpus, kw):
        """KV-cache decode must reproduce the training forward: greedy
        generation equals iteratively running the full forward and taking
        argmax of the last position's logits."""
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = _tiny_config(**kw)
        with jax.default_device(cpus[0]):
            params = tlm.init(jax.random.PRNGKey(3), cfg)
            rng = np.random.default_rng(0)
            prompt = jnp.asarray(rng.integers(0, 64, (2, 5)), jnp.int32)
            gen = tlm.generate(params, prompt, cfg, 6)

            toks = prompt
            for _ in range(6):
                logits = tlm.forward(params, toks, cfg)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(gen), np.asarray(toks[:, 5:]))

    def test_sampling_seeded_and_in_vocab(self, cpus):
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = _tiny_config()
        with jax.default_device(cpus[0]):
            params = tlm.init(jax.random.PRNGKey(0), cfg)
            prompt = jnp.zeros((2, 3), jnp.int32)
            g1 = tlm.generate(params, prompt, cfg, 8, temperature=1.0,
                              rng=jax.random.PRNGKey(7))
            g2 = tlm.generate(params, prompt, cfg, 8, temperature=1.0,
                              rng=jax.random.PRNGKey(7))
            g3 = tlm.generate(params, prompt, cfg, 8, temperature=1.0,
                              rng=jax.random.PRNGKey(8))
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
        assert not np.array_equal(np.asarray(g1), np.asarray(g3))
        assert np.asarray(g1).min() >= 0 and np.asarray(g1).max() < 64

    def test_top_k1_and_tiny_top_p_equal_greedy(self, cpus):
        """top_k=1 and a near-zero top_p both collapse sampling to the
        argmax token regardless of temperature."""
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = _tiny_config()
        with jax.default_device(cpus[0]):
            params = tlm.init(jax.random.PRNGKey(2), cfg)
            prompt = jnp.zeros((2, 3), jnp.int32)
            greedy = tlm.generate(params, prompt, cfg, 8)
            k1 = tlm.generate(params, prompt, cfg, 8, temperature=1.0,
                              top_k=1, rng=jax.random.PRNGKey(0))
            p_tiny = tlm.generate(params, prompt, cfg, 8, temperature=1.0,
                                  top_p=1e-9, rng=jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(k1), np.asarray(greedy))
        np.testing.assert_array_equal(np.asarray(p_tiny), np.asarray(greedy))

    def test_top_p_one_equals_plain_sampling(self, cpus):
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = _tiny_config()
        with jax.default_device(cpus[0]):
            params = tlm.init(jax.random.PRNGKey(2), cfg)
            prompt = jnp.zeros((2, 3), jnp.int32)
            plain = tlm.generate(params, prompt, cfg, 8, temperature=1.0,
                                 rng=jax.random.PRNGKey(5))
            p1 = tlm.generate(params, prompt, cfg, 8, temperature=1.0,
                              top_p=1.0, rng=jax.random.PRNGKey(5))
        np.testing.assert_array_equal(np.asarray(plain), np.asarray(p1))

    def test_top_k_ties_keep_exactly_k(self, cpus):
        """Tokens tied with the k-th logit must not leak into the candidate
        set: rank-based masking keeps exactly k (value-comparison masking
        kept every tied token)."""
        from petastorm_tpu.models import transformer_lm as tlm
        logits = jnp.asarray([[1.0, 1.0, 1.0, 0.0, -1.0]])
        seen = set()
        with jax.default_device(cpus[0]):
            for s in range(60):
                tok = tlm._sample_logits(logits, 1.0, 2, None,
                                         jax.random.PRNGKey(s))
                seen.add(int(tok[0]))
        assert len(seen) == 2 and seen <= {0, 1, 2}

    def test_top_p_ties_keep_minimal_set(self, cpus):
        """Uniform logits: exclusive-cumsum nucleus with top_p=0.5 keeps
        exactly the first two ranks; ties at the threshold must not widen
        the set."""
        from petastorm_tpu.models import transformer_lm as tlm
        logits = jnp.zeros((1, 4))
        seen = set()
        with jax.default_device(cpus[0]):
            for s in range(80):
                tok = tlm._sample_logits(logits, 1.0, None, 0.5,
                                         jax.random.PRNGKey(s))
                seen.add(int(tok[0]))
        assert len(seen) == 2

    def test_moe_decode_capacity_never_drops(self, cpus):
        """Decode routes with capacity = all units of the step, so a
        capacity_factor that would drop at per-step (B-unit) granularity
        still yields the dense no-drop oracle's output."""
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = _tiny_config(n_experts=4, moe_top_k=1, moe_capacity_factor=0.25)
        with jax.default_device(cpus[0]):
            params = tlm.init(jax.random.PRNGKey(1), cfg)
            layer = params['layers'][0]
            # force every token onto expert 0 to maximize contention
            layer['gate'] = jnp.zeros_like(layer['gate']).at[:, 0].set(10.0)
            x = jax.random.normal(jax.random.PRNGKey(2), (4, 1, 32),
                                  jnp.float32)
            oracle = tlm._moe_ffn_dense(x, layer, cfg)
            no_drop, _ = tlm._moe_ffn(x, layer, cfg,
                                      capacity=4 * cfg.moe_top_k)
            dropped, _ = tlm._moe_ffn(x, layer, cfg)   # default: capacity 1
        np.testing.assert_allclose(np.asarray(no_drop), np.asarray(oracle),
                                   rtol=1e-5, atol=1e-5)
        # documents why the override matters: default capacity drops 3/4 units
        assert not np.allclose(np.asarray(dropped), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)

    def test_bad_sampling_params_rejected(self, cpus):
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = _tiny_config()
        params = tlm.init(jax.random.PRNGKey(0), cfg)
        prompt = jnp.zeros((1, 2), jnp.int32)
        with pytest.raises(ValueError, match='top_k'):
            tlm.generate(params, prompt, cfg, 2, temperature=1.0, top_k=0)
        with pytest.raises(ValueError, match='top_p'):
            tlm.generate(params, prompt, cfg, 2, temperature=1.0, top_p=1.5)

    def test_windowed_model_greedy_matches_teacher_forced(self, cpus):
        """attention_window must be honored consistently by the training
        forward AND the KV-cache decode — greedy generation equals
        teacher-forcing the windowed forward."""
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = _tiny_config(attention_window=8)
        with jax.default_device(cpus[0]):
            params = tlm.init(jax.random.PRNGKey(3), cfg)
            rng = np.random.default_rng(0)
            prompt = jnp.asarray(rng.integers(0, 64, (2, 5)), jnp.int32)
            gen = tlm.generate(params, prompt, cfg, 10)
            toks = prompt
            for _ in range(10):
                logits = tlm.forward(params, toks, cfg)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
            # windowed and full-attention streams must actually differ
            full = tlm.generate(params, prompt,
                                _tiny_config(), 10)
        np.testing.assert_array_equal(np.asarray(gen),
                                      np.asarray(toks[:, 5:]))
        assert not np.array_equal(np.asarray(gen), np.asarray(full))

    def test_generate_jits(self, cpus):
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = _tiny_config()
        with jax.default_device(cpus[0]):
            params = tlm.init(jax.random.PRNGKey(0), cfg)
            fn = jax.jit(lambda p, t: tlm.generate(p, t, cfg, 4))
            out = fn(params, jnp.zeros((1, 2), jnp.int32))
        assert out.shape == (1, 4)


class TestGroupedQueryAttention:
    def test_gqa_ring_train_step(self, cpus):
        """GQA composes with ring attention: kv chunks rotate with the
        reduced head count (or the jnp path repeats internally)."""
        from petastorm_tpu.models import transformer_lm as tlm
        from petastorm_tpu.parallel import make_mesh
        cfg = _tiny_config(n_kv_heads=2, attention='ring')
        mesh = make_mesh({'data': 2, 'seq': 4},
                         devices=jax.devices('cpu')[:8])
        params = tlm.init(jax.random.PRNGKey(0), cfg)
        opt, step = tlm.make_train_step(cfg, mesh)
        st = opt.init(params)
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, 64, (4, 32)), jnp.int32)
        params, st, loss = step(params, st, toks, jnp.roll(toks, -1, 1))
        assert np.isfinite(float(loss))

    def test_gqa_train_step_and_kv_param_shapes(self, cpus):
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = _tiny_config(n_kv_heads=2)     # 4 q heads over 2 kv heads
        with jax.default_device(cpus[0]):
            params = tlm.init(jax.random.PRNGKey(0), cfg)
            assert params['layers'][0]['wk'].shape == (
                cfg.d_model, 2 * cfg.head_dim)
            opt, step = tlm.make_train_step(cfg)
            st = opt.init(params)
            rng = np.random.default_rng(0)
            toks = jnp.asarray(rng.integers(0, 64, (2, 32)), jnp.int32)
            params2, _, loss = step(params, st, toks, jnp.roll(toks, -1, 1))
        assert np.isfinite(float(loss))
        wk0 = np.asarray(params['layers'][0]['wk'])
        wk1 = np.asarray(params2['layers'][0]['wk'])
        assert not np.array_equal(wk0, wk1)  # kv projection received grads

    def test_gqa_flash_and_blockwise_agree(self, cpus):
        """On CPU both attention modes reduce to repeated-kv blockwise, so
        the model forward must be identical — pins the repeat semantics."""
        if jax.default_backend() != 'cpu':
            # flash_attention resolves its backend from the session default,
            # not array placement: on a TPU-attached host the 'flash' config
            # would lower Pallas for the CPU-pinned arrays and fail
            pytest.skip('CPU-equivalence premise needs a cpu default backend')
        from petastorm_tpu.models import transformer_lm as tlm
        rng = np.random.default_rng(1)
        toks = jnp.asarray(rng.integers(0, 64, (2, 32)), jnp.int32)
        with jax.default_device(cpus[0]):
            outs = []
            for attn in ('blockwise', 'flash'):
                cfg = _tiny_config(n_kv_heads=1, attention=attn)
                params = tlm.init(jax.random.PRNGKey(0), cfg)
                outs.append(np.asarray(tlm.forward(params, toks, cfg)))
        np.testing.assert_allclose(outs[0], outs[1], atol=1e-5)

    def test_bad_kv_head_ratio_rejected(self):
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = _tiny_config(n_kv_heads=3)     # 4 % 3 != 0
        with pytest.raises(ValueError, match='multiple of n_kv_heads'):
            tlm.init(jax.random.PRNGKey(0), cfg)

    @pytest.mark.parametrize('top_k', [0, 5])
    def test_bad_moe_top_k_rejected(self, top_k):
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = _tiny_config(n_experts=4, moe_top_k=top_k)
        with pytest.raises(ValueError, match='moe_top_k'):
            tlm.init(jax.random.PRNGKey(0), cfg)


class TestMoeDispatch:
    def _layer_and_x(self, cfg, rng_seed=0, batch=2, seq=16):
        from petastorm_tpu.models import transformer_lm as tlm
        params = tlm.init(jax.random.PRNGKey(rng_seed), cfg)
        layer = params['layers'][0]
        rng = np.random.default_rng(rng_seed)
        x = jnp.asarray(rng.standard_normal((batch, seq, cfg.d_model)) * 0.3,
                        jnp.float32)
        return layer, x

    @pytest.mark.parametrize('top_k', [1, 2])
    def test_sparse_matches_dense_oracle_with_ample_capacity(self, cpus,
                                                             top_k):
        # capacity_factor = n_experts → capacity = all dispatch units:
        # nothing can be dropped, so sort/scatter dispatch must reproduce the
        # dense one-hot oracle exactly (k=1 Switch and k=2 GShard routing)
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = _tiny_config(n_experts=4, moe_capacity_factor=4.0,
                           moe_top_k=top_k)
        layer, x = self._layer_and_x(cfg)
        with jax.default_device(cpus[0]):
            sparse, aux = tlm._moe_ffn(x, layer, cfg)
            dense = tlm._moe_ffn_dense(x, layer, cfg)
        np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                                   atol=1e-5)
        # Switch aux loss is minimized at 1.0 for perfectly uniform routing
        assert float(aux) >= 1.0 - 1e-5

    def test_top2_scales_normalized_and_token_uses_two_experts(self, cpus):
        """k=2: a token's two expert outputs are combined with weights that
        sum to 1; with only 2 experts and ample capacity nothing is dropped,
        so the result equals the full softmax-weighted two-expert mix."""
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = _tiny_config(n_experts=2, moe_capacity_factor=2.0, moe_top_k=2)
        layer, x = self._layer_and_x(cfg)
        with jax.default_device(cpus[0]):
            sparse, _ = tlm._moe_ffn(x, layer, cfg)
            # with E == k == 2 every token uses both experts, weights =
            # softmax probs renormalized over both = the probs themselves
            logits = x.astype(jnp.float32) @ layer['gate']
            probs = jax.nn.softmax(logits, axis=-1)
            outs = []
            for e_i in range(2):
                gate = jax.nn.silu(x @ layer['w_gate'][e_i].astype(x.dtype))
                up = x @ layer['w_up'][e_i].astype(x.dtype)
                outs.append((gate * up) @ layer['w_down'][e_i].astype(x.dtype))
            ref = (outs[0] * probs[..., 0:1].astype(x.dtype)
                   + outs[1] * probs[..., 1:2].astype(x.dtype))
        np.testing.assert_allclose(np.asarray(sparse), np.asarray(ref),
                                   atol=1e-5)

    def test_over_capacity_tokens_pass_through_as_zeros(self, cpus):
        from petastorm_tpu.models import transformer_lm as tlm
        # capacity 1 with 32 tokens over 2 experts: nearly all tokens dropped
        cfg = _tiny_config(n_experts=2, moe_capacity_factor=2 * 1.0 / 32)
        layer, x = self._layer_and_x(cfg)
        with jax.default_device(cpus[0]):
            out = np.asarray(tlm._moe_ffn(x, layer, cfg)[0])
        flat = out.reshape(-1, cfg.d_model)
        zero_rows = np.all(flat == 0.0, axis=1).sum()
        assert zero_rows >= flat.shape[0] - 2    # ≤1 kept per expert

    def test_flops_independent_of_expert_count(self, cpus):
        # The cost analysis must show per-token FLOPs ~constant in E: the
        # dense one-hot dispatch scaled linearly (VERDICT weak-item 6).
        from petastorm_tpu.models import transformer_lm as tlm

        def moe_flops(n_experts):
            cfg = _tiny_config(n_experts=n_experts, moe_capacity_factor=1.0)
            layer, x = self._layer_and_x(cfg)
            fn = jax.jit(lambda x: tlm._moe_ffn(x, layer, cfg)[0])
            return fn.lower(x).compile().cost_analysis()['flops']

        f2, f8 = moe_flops(2), moe_flops(8)
        assert f8 < f2 * 1.5, (f2, f8)   # dense dispatch would give ~4x

    @pytest.mark.parametrize('top_k', [1, 2])
    def test_grad_flows_and_sharded_step_runs(self, cpus, top_k):
        from jax.sharding import NamedSharding, PartitionSpec
        from petastorm_tpu.models import transformer_lm as tlm
        from petastorm_tpu.parallel import make_mesh
        cfg = _tiny_config(n_experts=4, moe_top_k=top_k)
        mesh = make_mesh({'data': 2, 'expert': 4}, devices=cpus[:8])
        params = tlm.init(jax.random.PRNGKey(0), cfg)
        pspecs = tlm.param_specs(cfg, mesh)
        p_shard = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), pspecs,
            is_leaf=lambda s: isinstance(s, PartitionSpec))
        params = jax.tree_util.tree_map(jax.device_put, params, p_shard)
        optimizer, step = tlm.make_train_step(cfg, mesh)
        opt_state = optimizer.init(params)
        rng = np.random.default_rng(0)
        b_shard = NamedSharding(mesh, tlm.batch_spec(mesh))
        toks = jax.device_put(jnp.asarray(
            rng.integers(0, 64, (4, 32)), jnp.int32), b_shard)
        tgts = jax.device_put(jnp.asarray(
            rng.integers(0, 64, (4, 32)), jnp.int32), b_shard)
        params2, _, loss1 = step(params, opt_state, toks, tgts)
        assert np.isfinite(float(loss1))
        # gate gradient reached the router (params actually changed)
        g0 = np.asarray(params['layers'][0]['gate'])
        g1 = np.asarray(params2['layers'][0]['gate'])
        assert not np.allclose(g0, g1)


class TestGraftEntry:
    def test_entry_and_dryrun(self, cpus):
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            'graft_entry', os.path.join(os.path.dirname(__file__), '..',
                                        '__graft_entry__.py'))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        fn, args = mod.entry()
        with jax.default_device(cpus[0]):
            out = fn(*args)
        assert out.shape == (2, 64, 256)
        mod.dryrun_multichip(8)

    def test_factor_axes(self):
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            'graft_entry2', os.path.join(os.path.dirname(__file__), '..',
                                         '__graft_entry__.py'))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        for n in (1, 2, 4, 6, 8, 16, 24, 81, 245, 256):
            axes = mod._factor_axes(n)
            assert np.prod(list(axes.values())) == n, (n, axes)
            assert axes['model'] in (1, 2, 4)
            assert axes['seq'] in (1, 2, 4, 8)
