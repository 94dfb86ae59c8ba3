"""End-to-end behaviour of the paper's system (Figure 1 pipeline) plus the
framework glue: launcher drivers, flash attention, input specs."""
import numpy as np

from repro.core import BoosterConfig, train, predict_proba
from repro.data import make_dataset


def test_paper_pipeline_on_paper_shaped_data():
    """Reduced-size higgs-like data through the full pipeline: quantise ->
    compress -> boost -> predict. The paper's Table 2 metric (accuracy)
    must beat a decision stump by a clear margin."""
    x, y, spec = make_dataset("higgs", n_rows=3000)
    cfg = BoosterConfig(n_rounds=15, max_depth=5, objective=spec.objective,
                        max_bins=128)
    st = train(x, y, cfg)
    p = np.asarray(predict_proba(st.ensemble, x, cfg.max_depth, cfg.objective))
    acc = float(np.mean((p > 0.5) == y))

    stump_cfg = BoosterConfig(n_rounds=1, max_depth=1, objective=spec.objective,
                              max_bins=128)
    st0 = train(x, y, stump_cfg)
    p0 = np.asarray(predict_proba(st0.ensemble, x, 1, spec.objective))
    acc0 = float(np.mean((p0 > 0.5) == y))
    assert acc > acc0 + 0.08, (acc, acc0)
    # compression engaged (paper §2.2): 8-bit bins -> >= 4x vs fp32
    assert st.matrix.compression_ratio() >= 4.0


def test_sparse_dataset_trains():
    """bosch-like 81%-missing data must train (sparsity-aware splits)."""
    x, y, spec = make_dataset("bosch", n_rows=1500)
    x = x[:, :64]  # column subset for CPU speed
    cfg = BoosterConfig(n_rounds=8, max_depth=4, objective=spec.objective,
                        max_bins=32)
    st = train(x, y, cfg)
    p = np.asarray(predict_proba(st.ensemble, x, 4, spec.objective))
    assert np.isfinite(p).all()
    assert float(np.mean((p > 0.5) == y)) > 0.55


def test_lm_train_loop_improves():
    """Deliverable (b): the LM trainer drives loss down on a reduced arch."""
    from repro.configs import get_arch
    from repro.launch.train import train_loop

    cfg = get_arch("yi-6b").reduced()
    _, hist = train_loop(cfg, steps=12, batch=4, seq=64, lr=3e-3, log_every=4)
    assert hist[-1]["loss"] < hist[0]["loss"], hist


def test_input_specs_cover_all_pairs():
    """Every supported (arch x shape) pair produces well-formed specs."""
    from repro.configs import ARCHS, get_arch
    from repro.launch import specs as SP
    from repro.models.config import SHAPES

    n_ok = n_skip = 0
    for arch in ARCHS:
        cfg = get_arch(arch)
        for shape in SHAPES.values():
            ok, reason = SP.supports_shape(cfg, shape)
            if not ok:
                n_skip += 1
                assert reason
                continue
            n_ok += 1
            specs = SP.input_specs(cfg, shape)
            assert "tokens" in specs
            b = shape.global_batch
            for v in specs.values():
                assert v.shape[0] == b
            if shape.kind == "decode":
                assert specs["tokens"].shape == (b, 1)
                cap = SP.cache_capacity(cfg, shape)
                assert 0 < cap <= shape.seq_len
    assert n_ok == 39 and n_skip == 1, (n_ok, n_skip)  # seamless long_500k


def test_gbdt_driver_cli(tmp_path):
    """train_gbdt driver end to end (single device)."""
    import subprocess, sys, os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.train_gbdt", "--dataset", "higgs",
         "--rows", "2000", "--rounds", "5", "--max-bins", "32",
         "--checkpoint", str(tmp_path / "ens.msgpack")],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stderr
    assert "valid_accuracy=" in res.stdout
    assert (tmp_path / "ens.msgpack").exists()
