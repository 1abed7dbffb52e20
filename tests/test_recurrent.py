"""Recurrent stack specs vs PyTorch oracle (reference LSTMSpec/GRUSpec
torch-oracle tests, SURVEY §4.2)."""
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")

from bigdl_tpu import nn
from bigdl_tpu.nn.recurrent import (
    GRU, LSTM, BiRecurrent, ConvLSTMPeephole, LSTMPeephole, Recurrent,
    RnnCell, TimeDistributed,
)

X = np.random.RandomState(3).randn(2, 5, 4).astype(np.float32)  # (N, T, F)


def test_rnn_cell_matches_torch():
    m = Recurrent(RnnCell(4, 6))
    t = torch.nn.RNN(4, 6, batch_first=True)
    cp = m.cell.params
    with torch.no_grad():
        t.weight_ih_l0.copy_(torch.tensor(np.asarray(cp["i2h"])))
        t.weight_hh_l0.copy_(torch.tensor(np.asarray(cp["h2h"])))
        t.bias_ih_l0.copy_(torch.tensor(np.asarray(cp["bias"])))
        t.bias_hh_l0.zero_()
    y = m.forward(jnp.asarray(X))
    yt, _ = t(torch.tensor(X))
    np.testing.assert_allclose(np.asarray(y), yt.detach().numpy(), atol=1e-5)


def test_lstm_matches_torch():
    m = Recurrent(LSTM(4, 6))
    t = torch.nn.LSTM(4, 6, batch_first=True)
    cp = m.cell.params
    H = 6
    # our gate order (i, f, z, o); torch order (i, f, g, o) — same!
    with torch.no_grad():
        t.weight_ih_l0.copy_(torch.tensor(np.asarray(cp["i2h"])))
        t.weight_hh_l0.copy_(torch.tensor(np.asarray(cp["h2h"])))
        t.bias_ih_l0.copy_(torch.tensor(np.asarray(cp["bias"])))
        t.bias_hh_l0.zero_()
    y = m.forward(jnp.asarray(X))
    yt, _ = t(torch.tensor(X))
    np.testing.assert_allclose(np.asarray(y), yt.detach().numpy(), atol=1e-5)


def test_gru_matches_torch():
    m = Recurrent(GRU(4, 6))
    t = torch.nn.GRU(4, 6, batch_first=True)
    cp = m.cell.params
    with torch.no_grad():
        t.weight_ih_l0.copy_(torch.tensor(np.asarray(cp["i2h"])))
        t.weight_hh_l0.copy_(torch.tensor(np.asarray(cp["h2h"])))
        t.bias_ih_l0.copy_(torch.tensor(np.asarray(cp["bias"])))
        t.bias_hh_l0.zero_()
    y = m.forward(jnp.asarray(X))
    yt, _ = t(torch.tensor(X))
    # torch GRU: n = tanh(W_in x + b_in + r*(W_hn h + b_hn)); with b_hh=0
    # this matches our formulation exactly
    np.testing.assert_allclose(np.asarray(y), yt.detach().numpy(), atol=1e-5)


def test_lstm_backward_flows():
    m = Recurrent(LSTM(4, 6))
    gi = m.backward(jnp.asarray(X), jnp.ones((2, 5, 6)))
    assert gi.shape == X.shape
    _, grads = m.parameters()
    assert all(bool((g != 0).any()) for g in grads)


def test_lstm_peephole_runs():
    m = Recurrent(LSTMPeephole(4, 6))
    y = m.forward(jnp.asarray(X))
    assert y.shape == (2, 5, 6)


def test_birecurrent():
    m = BiRecurrent().add(LSTM(4, 6))
    y = m.forward(jnp.asarray(X))
    assert y.shape == (2, 5, 6)
    # must differ from unidirectional (reversed pass contributes)
    f = Recurrent(LSTM(4, 6))
    f.cell.set_param_tree(m.fwd.cell.param_tree())
    yf = f.forward(jnp.asarray(X))
    assert not np.allclose(np.asarray(y), np.asarray(yf))


def test_conv_lstm_peephole():
    m = Recurrent(ConvLSTMPeephole(3, 8, 3, 3))
    x = np.random.RandomState(4).randn(2, 4, 3, 6, 6).astype(np.float32)
    y = m.forward(jnp.asarray(x))
    assert y.shape == (2, 4, 8, 6, 6)


def test_time_distributed():
    m = TimeDistributed(nn.Linear(4, 3))
    y = m.forward(jnp.asarray(X))
    assert y.shape == (2, 5, 3)
    # equals applying linear per timestep
    lin = nn.Linear(4, 3)
    lin.set_param_tree(m.module.param_tree())
    per_t = np.stack([np.asarray(lin.forward(jnp.asarray(X[:, i])))
                      for i in range(5)], axis=1)
    np.testing.assert_allclose(np.asarray(y), per_t, atol=1e-6)


def test_simple_rnn_trains():
    """SimpleRNN LM smoke (reference models/rnn/): loss decreases."""
    from bigdl_tpu.dataset import Sample, array
    from bigdl_tpu.models.rnn import SimpleRNN
    from bigdl_tpu.optim import LocalOptimizer, SGD, max_iteration

    V, T = 20, 6
    rng = np.random.RandomState(0)
    seqs = rng.randint(0, V, (64, T + 1))
    samples = []
    for s in seqs:
        x = np.eye(V, dtype=np.float32)[s[:-1]]
        y = (s[1:] + 1).astype(np.float32)
        samples.append(Sample(x, y))
    model = SimpleRNN(V, 16, V)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True)
    opt = LocalOptimizer(model, array(samples), crit, batch_size=16)
    opt.set_optim_method(SGD(learning_rate=0.5))
    opt.set_end_when(max_iteration(30))
    opt.optimize()
    first_loss = None  # recompute losses
    out = model.forward(jnp.asarray(np.stack([s.feature for s in samples[:16]])))
    tgt = jnp.asarray(np.stack([s.label for s in samples[:16]]))
    final = crit.forward(out, tgt)
    assert final < np.log(V), f"LM loss {final} not below chance {np.log(V)}"


def test_recurrent_scans_inside_a_checked_shard_map():
    """jax 0.9 types a scan carry by the mesh axes it varies over: the
    zero initial state starts unvarying while the step's output varies
    like the batch-sharded input, so Recurrent must cast the carry
    before the scan (LSTM's tuple state included)."""
    import jax
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    x = jnp.asarray(np.random.RandomState(0).randn(4, 5, 4), jnp.float32)
    for cell in (RnnCell(4, 6), LSTM(4, 6), GRU(4, 6)):
        m = Recurrent(cell)
        params, buffers = m.param_tree(), m.buffer_tree()
        fwd = lambda p, x: m.apply_fn(p, buffers, x, False, None)[0]
        sharded = jax.jit(shard_map(
            fwd, mesh=mesh, in_specs=(P(), P("data")),
            out_specs=P("data")))(params, x)   # check_vma defaults True
        np.testing.assert_allclose(np.asarray(sharded),
                                   np.asarray(fwd(params, x)), atol=1e-5)
