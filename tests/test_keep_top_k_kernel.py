"""The choice of a row's k largest scores as a kernel
(``ops/kernels/topk.py``), through the Pallas interpreter: the mask
``kernels.top_k_mask`` returns is ``jax.lax.top_k``'s choice element for
element (ties to the lower index, -0.0 under +0.0), the count beside it is
the mask's, whatever the row block finds: no tie over a row's room (one
threshold a row), ties over it in some blocks (the running count under
``pl.when``), fewer than ``k`` live entries; with an -inf an entry like
any other and never kept (``live``), and with the passes stopped at a
causal block's last column (``causal``). A shape the kernel has no block
for runs ``plain_form``, and ``KeyIndexer``'s counter says which."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.ops import kernels
from mxnet_tpu.ops import transformer as tr
from mxnet_tpu.ops.kernels import topk


def _lax_choice(scores, k, live):
    """``jax.lax.top_k``'s entries of each row as a mask; under ``live``
    without the -inf ones."""
    _, idx = jax.lax.top_k(jnp.asarray(scores), min(k, scores.shape[-1]))
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    return want & (scores > -np.inf) if live else want


def _holds(scores, k, live=False, causal=False):
    keep, kept = kernels.top_k_mask(jnp.asarray(scores), k, live=live,
                                    causal=causal, interpret=True)
    keep, kept = np.asarray(keep), np.asarray(kept)
    assert keep.dtype == np.int8 and kept.dtype == np.int32
    assert kept.shape == scores.shape[:-1] + (1,)
    want = _lax_choice(scores, k, live)
    np.testing.assert_array_equal(keep != 0, want)
    np.testing.assert_array_equal(kept[..., 0], want.sum(-1))
    return keep


def _causal(scores):
    t = scores.shape[-1]
    return np.where(np.tril(np.ones((t, t), bool)), scores,
                    -np.inf).astype(np.float32)


def _quarters(seed, t, batch=2):
    """Scores rounded to quarters (dozens of ties a row) but for the rows
    from the 128th of the first sequence (none: blocks of their own, which
    take the threshold), one row one value throughout, one of both
    zeros."""
    rng = np.random.RandomState(seed)
    s = rng.randn(batch, t, t)
    quartered = np.round(s * 4) / 4
    quartered[0, 128:] = s[0, 128:]
    quartered[0, 5] = 0.25
    quartered[1, 7, ::2] = -0.0
    quartered[1, 7, 1::2] = 0.0
    return quartered.astype(np.float32)


@pytest.mark.parametrize("t,k", [(64, 16), (300, 128), (32, 64)])
@pytest.mark.parametrize("live", [False, True], ids=["any", "live"])
def test_shapes_without_a_block_take_the_plain_form(t, k, live):
    """``tests/test_dots3.py``'s three shapes through the kernel's entry:
    no whole lane rows, no block, ``plain_form``, the same choice (all of
    a row where it is no longer than ``k``)."""
    assert kernels.top_k_rows((2, t, t), k) is None
    _holds(_causal(_quarters(t, t)), k, live=live)


@pytest.mark.parametrize("t,k", [(256, 128), (512, 128), (512, 48)])
@pytest.mark.parametrize("live", [False, True], ids=["any", "live"])
def test_the_kernel_makes_lax_top_k_s_choice(t, k, live):
    """Whole lane rows: the kernel. Ties by the dozen in most blocks and
    none in one, a row of one value, -0.0 against +0.0, uncut rows and
    causal ones (fewer than ``k`` live entries before the k-th row)."""
    assert kernels.top_k_rows((2, t, t), k) == 128
    scores = _quarters(t, t)
    _holds(scores, k, live=live)
    _holds(_causal(scores), k, live=live)


@pytest.mark.parametrize("t,k", [(256, 128), (512, 48)])
def test_the_causal_stop_changes_nothing(t, k):
    scores = _causal(_quarters(t + 1, t))
    np.testing.assert_array_equal(
        _holds(scores, k, live=True, causal=True),
        _holds(scores, k, live=True))
    with pytest.raises(ValueError, match="causal is for square scores"):
        kernels.top_k_mask(jnp.asarray(scores), k, causal=True)


def test_continuous_scores_take_the_threshold_and_rows_of_ties_the_count():
    """No two scores of a row alike: no block's ties pass its room. Every
    score alike: every row keeps its first ``k``."""
    rng = np.random.RandomState(3)
    _holds(rng.randn(1, 128, 384).astype(np.float32), 100)
    keep = _holds(np.full((1, 64, 256), -1.5, np.float32), 130)
    assert keep[0, :, :130].all() and not keep[0, :, 130:].any()
    # -0.0 sorts under +0.0: the k largest are the +0.0s, lowest first
    zeros = np.where(np.arange(256) % 3 == 0, 0.0, -0.0).astype(np.float32)
    keep = _holds(np.tile(zeros, (1, 32, 1)), 50)
    assert keep[0, 0, np.arange(256) % 3 == 0][:50].all()
    assert not keep[0, 0, np.arange(256) % 3 != 0].any()


def test_keep_top_k_reaches_the_kernel_by_the_seam(monkeypatch):
    """``keep_top_k`` is the entry's mask as a bool, the kernel's where a
    test sets the seam; a call that would reach ``plain_form`` fails."""
    scores = _causal(_quarters(9, 256))
    want = _lax_choice(scores, 40, False)
    monkeypatch.setattr(kernels.common, "INTERPRET", True)

    def plain_form(*args, **kw):
        raise AssertionError("the plain form ran")

    monkeypatch.setattr(topk, "plain_form", plain_form)
    got = tr.keep_top_k(jnp.asarray(scores), 40)
    assert got.dtype == bool
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(tr.keep_top_k(jnp.asarray(scores), 40, live=True,
                                 causal=True)),
        want & (scores > -np.inf))


@pytest.mark.parametrize("t,impl", [(256, "pallas"), (300, "jnp")])
def test_the_indexer_counts_which_form_chooses(t, impl, monkeypatch):
    """``KeyIndexer`` at a length of whole lane rows counts ``pallas`` and
    runs the kernel (the seam), at any other ``jnp``; either keeps
    ``min(t + 1, topk)`` keys a row, none past the diagonal, the
    reference's choice."""
    from mxnet_tpu.models import dots3_reference as ref

    rng = np.random.RandomState(t)
    draw = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    ins = (draw(2, t, 32), draw(2, t, 64), 0.2 * draw(64, 32),
           0.2 * draw(16, 64), 1 + 0.1 * draw(16), 0.1 * draw(16),
           0.2 * draw(4, 64))
    monkeypatch.setattr(kernels.common, "INTERPRET", True)
    telemetry.reset()
    telemetry.enable()
    try:
        keep, count = tr.key_indexer(*ins, num_heads=4, rope_dim=8, topk=64,
                                     theta=8e7)
        index = telemetry.REGISTRY.get("attention.index_lowerings")
        assert index.value(heads=4, width=16, topk=64, rows=256,
                           impl=impl) == 1
        assert telemetry.total("attention.index_lowerings") == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    keep = np.asarray(keep)
    assert keep.dtype == np.int8 and keep.shape == (2, t, t)
    want = np.minimum(np.arange(t) + 1, 64)
    np.testing.assert_array_equal(keep.sum(-1), np.tile(want, (2, 1)))
    assert not np.triu(keep, 1).any()
    np.testing.assert_array_equal(np.asarray(count), [want.sum()] * 2)
    np.testing.assert_array_equal(
        keep != 0, np.asarray(ref.select(ref.index_scores(*ins, 8e7, 8), 64)))
