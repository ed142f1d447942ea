"""Embedding encoder + on-device vector index."""

import jax
import numpy as np
import pytest

from finchat_tpu.embed.encoder import EMBED_PRESETS, EmbeddingEncoder, init_bert_params
from finchat_tpu.embed.index import DeviceVectorIndex, VectorPoint
from finchat_tpu.models.tokenizer import ByteTokenizer


@pytest.fixture(scope="module")
def encoder():
    config = EMBED_PRESETS["bge-tiny"]
    params = init_bert_params(config, jax.random.key(0))
    return EmbeddingEncoder(config, params, ByteTokenizer())


def test_embeddings_normalized(encoder):
    out = encoder.embed_batch(["hello world", "rent payment"])
    assert out.shape == (2, encoder.dim)
    norms = np.linalg.norm(out, axis=-1)
    np.testing.assert_allclose(norms, 1.0, atol=1e-5)


def test_padding_invariance(encoder):
    """A text's embedding must not depend on which batch/bucket it rode in."""
    solo = encoder.embed_query("coffee shop purchase")
    batched = encoder.embed_batch(["coffee shop purchase", "x" * 100])[0]
    np.testing.assert_allclose(solo, batched, atol=2e-2)


def _point(uid, date, text, vec):
    return VectorPoint(
        id=f"{uid}-{text[:8]}-{date}",
        vector=np.asarray(vec, np.float32),
        payload={"page_content": text, "metadata": {"user_id": uid, "date": date}},
    )


def test_index_user_filter():
    index = DeviceVectorIndex(dim=4, initial_capacity=4)
    index.upsert([
        _point("alice", 100, "alice txn", [1, 0, 0, 0]),
        _point("bob", 100, "bob txn", [1, 0, 0, 0]),
    ])
    hits = index.query_points(np.asarray([1, 0, 0, 0], np.float32), limit=10, user_id="alice")
    assert [h.payload["page_content"] for h in hits] == ["alice txn"]


def test_index_date_filter():
    index = DeviceVectorIndex(dim=4, initial_capacity=4)
    index.upsert([
        _point("u", 100, "old", [1, 0, 0, 0]),
        _point("u", 900, "new", [1, 0, 0, 0]),
    ])
    hits = index.query_points(np.asarray([1, 0, 0, 0], np.float32), limit=10, user_id="u", date_gte=500)
    assert [h.payload["page_content"] for h in hits] == ["new"]


def test_index_ranking_and_limit():
    index = DeviceVectorIndex(dim=4, initial_capacity=8)
    index.upsert([
        _point("u", 0, "exact", [1, 0, 0, 0]),
        _point("u", 0, "close", [0.9, 0.1, 0, 0]),
        _point("u", 0, "far", [0, 0, 1, 0]),
    ])
    hits = index.query_points(np.asarray([1, 0, 0, 0], np.float32), limit=2, user_id="u")
    assert [h.payload["page_content"] for h in hits] == ["exact", "close"]


def test_index_growth_past_capacity():
    index = DeviceVectorIndex(dim=4, initial_capacity=2)
    points = [_point("u", i, f"t{i}", np.eye(4)[i % 4]) for i in range(10)]
    index.upsert(points)
    assert len(index) == 10
    hits = index.query_points(np.asarray([1, 0, 0, 0], np.float32), limit=100, user_id="u")
    assert len(hits) == 10


def test_index_empty():
    index = DeviceVectorIndex(dim=4)
    assert index.query_points(np.zeros(4, np.float32), limit=5, user_id="u") == []


@pytest.mark.parametrize("n_texts,rows", [(1, 1), (3, 4), (5, 8)])
def test_batch_is_padded_to_a_power_of_two(encoder, n_texts, rows):
    """Coalesced queries arrive in any count: the encoder sees a power-of-two
    batch (one program a bucket), and each text's embedding is what it is
    alone."""
    from finchat_tpu.embed import encoder as enc

    seen = []
    real = enc.encode_batch

    def spy(params, tokens, lengths, **kw):
        seen.append((tokens.shape, lengths.shape))
        return real(params, tokens, lengths, **kw)

    texts = [f"payment number {i} at the corner shop" for i in range(n_texts)]
    enc.encode_batch = spy
    try:
        out = encoder.embed_batch(texts)
    finally:
        enc.encode_batch = real
    assert seen == [((rows, 64), (rows,))]
    assert out.shape == (n_texts, encoder.dim)
    for i, text in enumerate(texts):
        np.testing.assert_allclose(out[i], encoder.embed_query(text), atol=2e-2)


@pytest.mark.parametrize("limit", [1, 3, 5, 6, 40])
def test_index_limit_is_cut_from_a_power_of_two_k(limit):
    """The compiled top-k takes k as a power of two (few programs whatever a
    model asks for); the host cuts each answer to its own limit, on both
    query planes."""
    from finchat_tpu.embed import index as ix
    from finchat_tpu.embed.index import QuerySpec

    idx = DeviceVectorIndex(dim=4, initial_capacity=16)
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(12, 4))
    idx.upsert([_point("u1", 1000 + i, f"row {i:02d}", vecs[i]) for i in range(12)])
    q = rng.normal(size=(4,))
    want = [p.payload["page_content"] for p in idx.query_points(q, limit=12, user_id="u1")][:limit]
    ks = []
    real, real_batch = ix._topk_scores, ix._topk_scores_batch
    ix._topk_scores = lambda *a, k: (ks.append(k), real(*a, k=k))[1]
    ix._topk_scores_batch = lambda *a, k: (ks.append(k), real_batch(*a, k=k))[1]
    try:
        serial = idx.query_points(q, limit=limit, user_id="u1")
        batch = idx.query_points_batch([
            QuerySpec(q, limit=limit, user_id="u1"), QuerySpec(q, limit=2, user_id="u1")])
    finally:
        ix._topk_scores, ix._topk_scores_batch = real, real_batch
    assert [p.payload["page_content"] for p in serial] == want
    assert [p.payload["page_content"] for p in batch[0]] == want
    assert len(batch[1]) == 2
    assert all(k & (k - 1) == 0 and k <= 16 for k in ks) and len(ks) == 2
