"""Document packing: variable-length token sequences → fixed-shape
(tokens, segment_ids, positions) batches, and the LM consuming them.

This is the host-side bridge between the data layer (NGram/token pipelines
emit variable-length documents) and the packed-attention kernels
(``tests/test_flash_segments.py`` pins the kernel contract). Packed training
on N documents must equal training on the same documents padded one-per-row.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from petastorm_tpu.packing import pack_documents



class TestPackDocuments:
    def test_basic_two_rows(self):
        docs = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10]]
        out = pack_documents(docs, seq_len=6)
        # greedy first-fit: [1,2,3|4,5|10] and [6,7,8,9|pad]
        assert out.tokens.shape == out.segment_ids.shape == out.positions.shape
        assert out.tokens.shape[1] == 6
        # host arrays: placing them on a device is the loader's job
        assert all(isinstance(a, np.ndarray) for a in out)
        for row_tok, row_seg, row_pos in zip(out.tokens, out.segment_ids,
                                             out.positions):
            # positions restart at 0 on every segment boundary
            for t in range(len(row_tok)):
                if row_seg[t] == 0:          # padding slot
                    continue
                if t == 0 or row_seg[t] != row_seg[t - 1]:
                    assert row_pos[t] == 0
                else:
                    assert row_pos[t] == row_pos[t - 1] + 1

    def test_round_trip_every_document_present(self):
        rng = np.random.default_rng(0)
        docs = [list(rng.integers(1, 100, rng.integers(1, 10)))
                for _ in range(37)]
        out = pack_documents(docs, seq_len=16)
        recovered = []
        for row_tok, row_seg in zip(out.tokens, out.segment_ids):
            for seg in range(1, int(row_seg.max()) + 1):
                sel = row_seg == seg
                if sel.any():
                    recovered.append(list(row_tok[sel]))
        assert sorted(map(tuple, recovered)) == sorted(map(tuple, docs))

    def test_padding_is_segment_zero(self):
        out = pack_documents([[1, 2]], seq_len=8, pad_token=0)
        seg = out.segment_ids[0]
        tok = out.tokens[0]
        assert (seg[:2] == 1).all() and (seg[2:] == 0).all()
        assert (tok[2:] == 0).all()

    def test_document_longer_than_seq_len_rejected(self):
        with pytest.raises(ValueError, match='seq_len'):
            pack_documents([[1] * 10], seq_len=8)

    def test_deterministic(self):
        docs = [[i] * (i % 5 + 1) for i in range(20)]
        a = pack_documents(docs, seq_len=12)
        b = pack_documents(docs, seq_len=12)
        np.testing.assert_array_equal(a.tokens, b.tokens)

    def test_num_rows_pins_batch_dim(self):
        """Jitted consumers need a static batch dim: num_rows pads with
        all-padding rows and rejects overflow."""
        out = pack_documents([[1, 2], [3]], seq_len=4, num_rows=4)
        assert out.tokens.shape == (4, 4)
        assert (out.segment_ids[1:] == 0).all() or \
               (out.segment_ids[-2:] == 0).all()
        with pytest.raises(ValueError, match='num_rows'):
            pack_documents([[1] * 4, [2] * 4, [3] * 4], seq_len=4, num_rows=2)


@pytest.mark.slow    # LM forward equivalence: minutes-scale
class TestPackedModelForward:
    def test_packed_equals_per_document(self):
        """Logits of packed documents must equal each document's logits run
        alone — segments isolate attention AND positions restart."""
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = tlm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                    n_layers=2, d_ff=64, max_seq_len=32,
                                    dtype=jnp.float32)
        params = tlm.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(1)
        docs = [list(rng.integers(0, 64, n)) for n in (6, 9, 4)]
        packed = pack_documents(docs, seq_len=24)

        logits = tlm.forward(params, packed.tokens, cfg,
                             positions=packed.positions,
                             segment_ids=packed.segment_ids)

        row_tok = packed.tokens[0]
        row_seg = packed.segment_ids[0]
        for seg_id in range(1, int(row_seg.max()) + 1):
            sel = row_seg == seg_id
            doc = jnp.asarray(row_tok[sel])[None, :]
            alone = tlm.forward(params, doc, cfg)
            np.testing.assert_allclose(
                np.asarray(logits[0][sel]), np.asarray(alone[0]),
                atol=1e-4, rtol=1e-4)

    def test_positions_derived_from_segments_when_omitted(self):
        """Passing segment_ids without positions must not silently continue
        the neighbor document's rotary offsets — forward derives restarting
        positions itself."""
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = tlm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                    n_layers=1, d_ff=64, max_seq_len=32,
                                    dtype=jnp.float32)
        params = tlm.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(7)
        packed = pack_documents(
            [list(rng.integers(0, 64, n)) for n in (6, 9, 4)], seq_len=16)
        explicit = tlm.forward(params, packed.tokens, cfg,
                               positions=packed.positions,
                               segment_ids=packed.segment_ids)
        derived = tlm.forward(params, packed.tokens, cfg,
                              segment_ids=packed.segment_ids)
        np.testing.assert_allclose(np.asarray(derived), np.asarray(explicit),
                                   atol=1e-6)

    def test_packed_loss_equals_per_document_loss(self):
        """loss_fn consuming a packed batch (positions + segment_ids +
        weights) equals the token-weighted mean of per-document losses."""
        from petastorm_tpu.models import transformer_lm as tlm
        from petastorm_tpu.packing import packed_lm_targets
        cfg = tlm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                    n_layers=2, d_ff=64, max_seq_len=32,
                                    dtype=jnp.float32)
        params = tlm.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(4)
        docs = [list(rng.integers(0, 64, n)) for n in (7, 5, 9)]
        packed = pack_documents(docs, seq_len=16, num_rows=2)
        targets, weights = packed_lm_targets(packed.tokens,
                                             packed.segment_ids)
        packed_loss = tlm.loss_fn(params, packed.tokens, targets, cfg,
                                  positions=packed.positions,
                                  segment_ids=packed.segment_ids,
                                  weights=weights)

        total_nll, total_tok = 0.0, 0
        for doc in docs:
            toks = jnp.asarray(doc, jnp.int32)[None]
            logits = tlm.forward(params, toks, cfg)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp[:, :-1], toks[:, 1:, None], axis=-1).squeeze(-1)
            total_nll += float(jnp.sum(nll))
            total_tok += len(doc) - 1
        np.testing.assert_allclose(float(packed_loss),
                                   total_nll / total_tok, rtol=1e-5)

    def test_packed_loss_masks_padding_and_boundaries(self):
        """packed_lm_targets: next-token targets within a segment; padding
        and the last token of each segment get weight 0."""
        from petastorm_tpu.packing import packed_lm_targets
        tokens = jnp.asarray([[1, 2, 3, 9, 8, 0, 0, 0]], jnp.int32)
        seg = jnp.asarray([[1, 1, 1, 2, 2, 0, 0, 0]], jnp.int32)
        targets, weights = packed_lm_targets(tokens, seg)
        np.testing.assert_array_equal(
            np.asarray(weights[0]), [1, 1, 0, 1, 0, 0, 0, 0])
        assert np.asarray(targets)[0, 0] == 2
        assert np.asarray(targets)[0, 3] == 8
