"""Subprocess smoke of user-facing example flows that no unit test
covers end to end. Kept tiny (short epochs), but each smoke is a fresh
interpreter + jax init + XLA compile, so the whole module rides in the
nightly `slow` tier (tests/README.md)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow


def _run_example(script, *args, timeout=420):
    """Run one example on the CPU backend; asserts exit 0 and returns
    its stdout (one shared implementation so env/timeouts can't drift
    between smokes)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    r = subprocess.run(
        [sys.executable, script, "--ctx", "cpu", *args],
        cwd=os.path.join(ROOT, "examples"), env=env,
        capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, (script, r.stderr[-2000:])
    return r.stdout


def test_char_lstm_trains_and_samples():
    """examples/char_lstm.py (reference example/rnn char-lstm flow):
    unrolled training + seq_len=1 stepwise inference with explicit
    LSTM state IO must run end to end and emit sampled text."""
    out = _run_example("char_lstm.py", "--num-epochs", "2",
                       "--sample-chars", "25", "--num-hidden", "64")
    assert "---- sampled ----" in out
    # 26 chars emitted (seed + 25 sampled); don't strip — trailing
    # sampled whitespace is legitimate output of a stochastic sampler
    sampled = out.split("---- sampled ----\n")[-1].rstrip("\n")
    assert len(sampled) >= 20, repr(sampled)


def test_olmoe_lm_trains_through_module_fit():
    """examples/train_olmoe_lm.py: the tiny preset through Module.fit's
    fused step learns its deterministic corpus and reports the expert
    rows of the last step."""
    out = _run_example("train_olmoe_lm.py", "--num-epochs", "3")
    first, last = (float(x) for x in out.split("loss ")[1].split(" (")[0]
                   .split(" -> "))
    assert last < first - 2.0, out
    assert "rows per expert" in out


def test_adversary_fgsm_drops_accuracy():
    """examples/adversary_fgsm.py (reference example/adversary): the
    inputs_need_grad Module path must deliver real dLoss/dData — FGSM
    perturbation at eps=0.15 must measurably hurt accuracy (the script
    asserts adv < clean internally; seeded, so deterministic)."""
    out = _run_example("adversary_fgsm.py", "--num-epochs", "4")
    assert "adversarial accuracy" in out


def test_autoencoder_reconstructs():
    """examples/autoencoder.py (reference example/autoencoder): the
    regression head + input-as-label flow must reconstruct digits well
    below input variance (the script asserts mse < 50% of variance)."""
    out = _run_example("autoencoder.py", "--num-epochs", "3")
    assert "reconstruction mse" in out


def test_matrix_factorization_recovers_low_rank():
    """examples/matrix_factorization.py (reference example/recommenders):
    embedding-dot regression must recover synthetic low-rank structure
    (script asserts mse < 20% of rating variance). Also a regression
    canary for the 1-d-prediction MSE metric fix."""
    out = _run_example("matrix_factorization.py", "--num-epochs", "8")
    assert "rating mse" in out


def test_bi_lstm_sort_learns():
    """examples/bi_lstm_sort.py (reference example/bi-lstm-sort): the
    BidirectionalCell unroll must train end to end; short smoke run
    only requires clearly-above-chance per-digit accuracy (full config
    reaches ~0.96)."""
    out = _run_example("bi_lstm_sort.py", "--num-epochs", "3",
                       "--num-samples", "1500", "--min-acc", "0.3")
    assert "per-digit sort accuracy" in out


def test_multi_task_both_heads_learn():
    """examples/multi_task.py (reference example/multi-task): a Group
    of two loss heads over a shared trunk — both heads' validation
    accuracies must clear 0.9 (asserted in-script)."""
    out = _run_example("multi_task.py", "--num-epochs", "8")
    assert "parity accuracy" in out


def test_svm_output_head_trains():
    """examples/svm_digits.py (reference example/svm_mnist): the
    SVMOutput hinge-loss head must train to >=0.9 (asserted in-script;
    both squared and L1 hinge variants share the path)."""
    out = _run_example("svm_digits.py")  # 12-epoch default: margin
    assert "svm accuracy" in out


def test_custom_numpy_op_trains():
    """examples/numpy_ops.py (reference example/numpy-ops): a user
    CustomOp (numpy softmax loss) in the training graph — forward AND
    backward in host python — must reach >=0.9 (asserted in-script)."""
    out = _run_example("numpy_ops.py")
    assert "custom-numpy-softmax accuracy" in out


def test_cnn_text_classification_learns_ngrams():
    """examples/cnn_text_classification.py (reference
    example/cnn_text_classification): multi-width conv branches over
    embeddings must solve a bigram-order task bag-of-words cannot
    (script asserts accuracy; 0.988 at 5 epochs)."""
    out = _run_example("cnn_text_classification.py", "--num-epochs", "4",
                       "--min-acc", "0.75", timeout=560)
    assert "sentence accuracy" in out


def test_nce_loss_learns_cooccurrence():
    """examples/nce_loss.py (reference example/nce-loss): sampled-
    negative training of a large-softmax embedding — nearest-neighbor
    same-group rate must crush chance (script asserts >=0.6; observed
    1.0 at 6 epochs)."""
    out = _run_example("nce_loss.py", "--num-epochs", "6")
    assert "same-group rate" in out


def test_sgld_matches_analytic_posterior():
    """examples/bayesian_sgld.py (reference example/bayesian-methods):
    the SGLD optimizer sampling Bayesian linear regression must match
    the CLOSED-FORM posterior (mean within 3.5 posterior stds, per-dim
    std within 35%; observed ratios 0.98-1.06) — a quantitative
    optimizer check, not just a smoke."""
    out = _run_example("bayesian_sgld.py")
    assert "SGLD matches the analytic posterior" in out


def test_reinforce_gridworld_improves():
    """examples/reinforce_gridworld.py (reference
    example/reinforcement-learning): the MakeLoss(-logpi * advantage)
    policy gradient must lift mean episode return well above the
    random-policy baseline (script asserts +0.5; observed -0.41 ->
    0.86)."""
    out = _run_example("reinforce_gridworld.py", "--iters", "35")
    assert "-> trained" in out


def test_stochastic_depth_trains_and_rescales():
    """examples/stochastic_depth.py (reference example/stochastic-depth):
    Bernoulli-gated residual branches (symbolic mx.sym.uniform) at train
    time, expectation-scaled at inference — the rescaled deterministic
    net must score >= the enforced --min-acc 0.8 from stochastically-
    trained weights (observed ~0.91 at the 22-epoch default)."""
    out = _run_example("stochastic_depth.py", "--min-acc", "0.8",
                       timeout=560)  # 22-epoch default, observed ~0.91
    assert "expectation-scaled" in out


def test_dec_clustering_pipeline():
    """examples/dec_clustering.py (reference example/dec): AE pretrain
    -> k-means init -> KL(P||Q) joint refinement with trainable
    centers; clustering accuracy (Hungarian map) must stay within
    tolerance of the k-means init and above 0.6 (asserted in-script)."""
    out = _run_example("dec_clustering.py", "--num-epochs", "15",
                       "--refine-rounds", "3", "--lr", "0.001",
                       timeout=560)
    assert "DEC refined acc" in out
