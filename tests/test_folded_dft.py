"""The folded tile DFT of the FFT engine.

Stages 1, 2 and 4 apply each tile's 2-D DFT as one matmul against a fixed
matrix (``repro.core.dft``): the forward folds the compact-Hermitian
packing in, the inverse folds the conj-mirror scatter and the overlap-save
crop in.  They must equal the separable chain they replace —
``pack_half_spectrum(*rfft2_tiles(x))`` and
``irfft2_tiles(*unpack_half_spectrum(z))`` — for even and odd tile sizes,
the matrices' width must be ``ConvSpec.freq_points()``, and every planned
layer must carry that many points through its stages.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.conv import Epilogue, plan_conv, stages
from repro.conv.analyze import analyze, seeded_violation
from repro.core import dft, fftconv as F
from repro.core.fftconv import make_spec

DELTAS = [5, 8, 16]


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def _assert_close(y, y0, tol):
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                               rtol=tol, atol=tol)


def _spec(delta, B=2, C=3, Co=4, H=13, W=11, k=3, pad=1):
    k = min(k, delta)
    return make_spec((B, C, H, W), (Co, C, k, k), padding=pad, delta=delta)


@pytest.mark.parametrize("delta", DELTAS)
def test_folded_forward_matrix_equals_packed_rfft2(delta):
    x = _rand((7, delta, delta), delta)
    A = dft.compact_forward_mat(delta)
    P = dft.num_freq_real(delta)
    assert A.shape == (delta * delta, 2 * P)
    T = jnp.dot(x.reshape(7, -1), A, precision=dft.PRECISION)
    Tr0, Ti0 = dft.pack_half_spectrum(*dft.rfft2_tiles(x, delta), delta)
    scale = float(jnp.abs(Tr0).max())
    _assert_close(T[:, :P], Tr0, 2e-6 * scale)
    _assert_close(T[:, P:], Ti0, 2e-6 * scale)


@pytest.mark.parametrize("delta", DELTAS)
def test_folded_input_transform_equals_separable(delta):
    """Stage 1: channels-last tiles against the folded matrix, laid out as
    the (P, M, C) the CGEMM reads."""
    spec = _spec(delta)
    x = _rand((spec.B, spec.C, spec.H, spec.W), 1)
    Dr, Di = F.input_transform(x, spec)
    tiles = F.extract_tiles(x, spec)                  # (B, C, X, Dl, d, d)
    Tr, Ti = dft.pack_half_spectrum(*dft.rfft2_tiles(tiles, delta), delta)
    P = Tr.shape[-1]

    def to_pmc(T):
        return T.transpose(4, 0, 2, 3, 1).reshape(P, spec.M, spec.C)
    scale = float(jnp.abs(Tr).max())
    _assert_close(Dr, to_pmc(Tr), 2e-6 * scale)
    _assert_close(Di, to_pmc(Ti), 2e-6 * scale)


@pytest.mark.parametrize("delta", DELTAS)
@pytest.mark.parametrize("pad_rows", [0, 3])
def test_folded_inverse_equals_unpacked_irfft2(delta, pad_rows):
    """Stage 4: ``zr @ Kr + zi @ Ki`` with the crop folded in, against the
    conj-mirror unpack + separable irfft2 + crop; rows past P_real (the
    nfft all-to-all padding) are ignored."""
    spec = _spec(delta, k=3 if delta > 5 else 2)
    P = dft.num_freq_real(delta)
    Zr = _rand((P + pad_rows, spec.M, spec.Cout), 2)
    Zi = _rand((P + pad_rows, spec.M, spec.Cout), 3)
    y = F.output_inverse(Zr, Zi, spec)
    Ur, Ui = dft.unpack_half_spectrum(F.z_to_flat_tiles(Zr, spec, P),
                                      F.z_to_flat_tiles(Zi, spec, P), delta)
    y0 = F.assemble_output_tiles(dft.irfft2_tiles(Ur, Ui, delta), spec)
    assert y.shape == (spec.B, spec.Cout, spec.Ho, spec.Wo)
    _assert_close(y, y0, 2e-6 * float(jnp.abs(y0).max()))


@pytest.mark.parametrize("delta", DELTAS)
def test_cropped_inverse_is_the_full_inverse_cropped(delta):
    t_h, t_w = delta - 2, delta - 1
    Kr, Ki = dft._compact_inverse_np(delta)
    Cr, Ci = dft._cropped_inverse_np(delta, t_h, t_w)
    for K, C in ((Kr, Cr), (Ki, Ci)):
        np.testing.assert_array_equal(
            C, K.reshape(-1, delta, delta)[:, :t_h, :t_w].reshape(len(K), -1))


def test_rect_folded_forward_keeps_every_half_plane_point():
    d = 8
    x = _rand((3, d, d), 4)
    A = dft._folded_forward_np(d, compact=False)
    P = d * (d // 2 + 1)
    assert A.shape == (d * d, 2 * P)
    T = x.reshape(3, -1) @ jnp.asarray(A)
    Tr, Ti = dft.rfft2_tiles(x, d)
    _assert_close(T[:, :P], Tr.reshape(3, -1), 1e-4)
    _assert_close(T[:, P:], Ti.reshape(3, -1), 1e-4)


@pytest.mark.parametrize("delta", [5, 8, 15, 16, 32])
def test_freq_points_is_the_matrices_width(delta):
    """``ConvSpec.freq_points()`` counts the points the folded matrices
    produce (forward columns) and consume (inverse rows)."""
    spec = _spec(delta, H=2 * delta, W=2 * delta)
    assert spec.freq_points() \
        == dft.compact_forward_mat(delta).shape[1] // 2 \
        == dft.cropped_inverse_mats(delta, spec.t_h, spec.t_w)[0].shape[0]


@pytest.mark.parametrize("delta", DELTAS)
@pytest.mark.parametrize("backend", ["fft-xla", "fft-pallas"])
def test_planned_real_layer_records_the_folded_form(delta, backend,
                                                    monkeypatch):
    """Stage 1's output, the CGEMM's operands and stage 4's input of a
    planned layer all carry ``spec.freq_points()`` points, as does the
    prepared kernel."""
    spec = _spec(delta)
    plan = plan_conv((spec.B, spec.C, spec.H, spec.W),
                     (spec.Cout, spec.C, spec.kh, spec.kw), padding=1,
                     delta=delta, backend=backend, schedule="local",
                     epilogue=Epilogue(bias=True, activation="relu"),
                     cache=False)
    prepared = plan.prepare(_rand((spec.Cout, spec.C, spec.kh, spec.kw), 5))
    points = {}

    def record(name, points_of):
        """Wrap a stage op to note the point count it sees."""
        fn = getattr(stages, name)

        def wrapped(*args, **kw):
            out = fn(*args, **kw)
            points.setdefault(name, set()).add(int(points_of(args, out)))
            return out
        monkeypatch.setattr(stages, name, wrapped)

    record("stage_input_transform", lambda args, out: out[0].shape[0])
    record("stage_cgemm", lambda args, out: args[2].shape[0])       # Gr
    record("stage_output_inverse", lambda args, out: args[0].shape[0])
    x = _rand((spec.B, spec.C, spec.H, spec.W), 6)
    jax.make_jaxpr(lambda x, b: prepared(x, bias=b))(
        x, jnp.ones((spec.Cout,)))
    P = spec.freq_points()
    assert points == {"stage_input_transform": {P}, "stage_cgemm": {P},
                      "stage_output_inverse": {P}}
    assert {leaf.shape[0] for leaf in
            jax.tree_util.tree_leaves(prepared.state)} == {P}
    assert analyze(prepared).check().ok


@pytest.mark.parametrize("schedule,invariant", [
    ("nfft", "nfft-collective-bytes"), ("wfft", "wfft-collective-bytes")])
def test_rfft_unpacked_seed_trips_the_halves_invariants(schedule, invariant):
    """The seeded rect-layout folded forward ships the redundant rows: the
    exact collective-bytes invariant of each sharded schedule must fail."""
    mesh = make_mesh((1, 1), ("data", "model"))
    with seeded_violation("rfft-unpacked"):
        p = analyze(plan_conv((2, 4, 22, 22), (4, 4, 3, 3), padding=1,
                              backend="fft-xla", schedule=schedule,
                              mesh=mesh, cache=False))
    names = [v.invariant for v in p.check().violations]
    assert invariant in names
    assert dft.compact_forward_mat(16).shape == (256, 260)   # restored
