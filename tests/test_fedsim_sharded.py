"""Client-sharded scan engine: parity with the fused engine on a real
multi-device client mesh (forced host devices), single-donated-executable
invariants, and the mesh-validation errors."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs.paper_cnn import CNNConfig
from repro.core.fedsim import FederatedSimulation, FedSimConfig, METHODS
from repro.data import (dirichlet_partition, make_client_datasets,
                        synthetic_image_dataset, train_test_split)


def _run(code: str, timeout: int = 600) -> str:
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       cwd="/root/repo", env=env)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    return r.stdout


def _tiny_setup(n_clients=4, seed=0):
    model_cfg = CNNConfig(image_size=8, widths=(4,), hidden=16, n_classes=4)
    base = synthetic_image_dataset(seed, 600, image_size=8, n_classes=4)
    parts = dirichlet_partition(base.y, n_clients, alpha=0.3, seed=seed)
    train = make_client_datasets(
        base, [train_test_split(p, seed=1)[0] for p in parts])
    test = make_client_datasets(
        base, [train_test_split(p, seed=1)[1] for p in parts])
    pm = np.array([True] * (n_clients - 1) + [False])
    p_err = np.linspace(0.0, 0.2, n_clients).astype(np.float32)
    return model_cfg, train, test, pm, p_err


def _cfg(**kw):
    base = dict(rounds=3, batch_size=16, lr=0.05, em_iters=2, em_subset=64,
                adapt_subset=32, eval_every=2, seed=0)
    base.update(kw)
    return FedSimConfig(**base)


def test_sharded_matches_fused_on_four_devices():
    """All six methods: the client-sharded engine on a real 4-device
    ("clients",) mesh reproduces the fused trajectory on identical seeds
    (needs >1 device => subprocess with forced host devices)."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np
        from repro.configs.paper_cnn import CNNConfig
        from repro.core.fedsim import (FederatedSimulation, FedSimConfig,
                                       METHODS)
        from repro.data import (dirichlet_partition, make_client_datasets,
                                synthetic_image_dataset, train_test_split)

        mc = CNNConfig(image_size=8, widths=(4,), hidden=16, n_classes=4)
        base = synthetic_image_dataset(0, 600, image_size=8, n_classes=4)
        parts = dirichlet_partition(base.y, 4, alpha=0.3, seed=0)
        train = make_client_datasets(
            base, [train_test_split(p, seed=1)[0] for p in parts])
        test = make_client_datasets(
            base, [train_test_split(p, seed=1)[1] for p in parts])
        pm = np.array([True, True, True, False])
        p_err = np.linspace(0.0, 0.2, 4).astype(np.float32)

        def cfg(**kw):
            return FedSimConfig(rounds=3, batch_size=16, lr=0.05, em_iters=2,
                                em_subset=64, adapt_subset=32, eval_every=2,
                                seed=0, **kw)

        fused = FederatedSimulation(mc, train, test, pm, p_err, cfg())
        sharded = FederatedSimulation(mc, train, test, pm, p_err,
                                      cfg(sharded=True, shard_devices=4))
        for method in METHODS:
            hf, hs = fused.run(method), sharded.run(method)
            np.testing.assert_allclose(hs["target_acc"], hf["target_acc"],
                                       atol=5e-3, err_msg=method)
            np.testing.assert_allclose(hs["mean_participant_acc"],
                                       hf["mean_participant_acc"],
                                       atol=5e-3, err_msg=method)
            if method == "pfedwn":
                np.testing.assert_allclose(np.stack(hs["pi"]),
                                           np.stack(hf["pi"]), atol=1e-4)
            assert sharded.last_run_stats["engine"] == "sharded"
        print("SHARDED_PARITY_OK")
    """)
    assert "SHARDED_PARITY_OK" in out


def test_sharded_block_is_single_clean_executable():
    """With taps on, a sharded round block lowers to ONE donated executable:
    no host callbacks/infeed/outfeed, the rounds scanned inside it, the
    cross-client exchange visible as real collectives (psum -> all-reduce;
    pfedwn's single per-round peer gather -> all-gather)."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np
        from repro.configs.paper_cnn import CNNConfig
        from repro.core.fedsim import FederatedSimulation, FedSimConfig
        from repro.data import (dirichlet_partition, make_client_datasets,
                                synthetic_image_dataset, train_test_split)

        mc = CNNConfig(image_size=8, widths=(4,), hidden=16, n_classes=4)
        base = synthetic_image_dataset(0, 600, image_size=8, n_classes=4)
        parts = dirichlet_partition(base.y, 4, alpha=0.3, seed=0)
        train = make_client_datasets(
            base, [train_test_split(p, seed=1)[0] for p in parts])
        test = make_client_datasets(
            base, [train_test_split(p, seed=1)[1] for p in parts])
        pm = np.array([True, True, True, False])
        p_err = np.linspace(0.0, 0.2, 4).astype(np.float32)
        sim = FederatedSimulation(
            mc, train, test, pm, p_err,
            FedSimConfig(rounds=3, batch_size=16, em_iters=2, em_subset=64,
                         adapt_subset=32, eval_every=2, taps=True,
                         sharded=True, shard_devices=4))
        state = sim.initial_state()
        data = sim.staged_data()
        from repro.lint import hlo as lint_hlo
        for method, wants_gather in (("fedavg", False), ("pfedwn", True)):
            lowered = sim.block_fn(method).lower(state, data, 3)
            # shared analyzer: no host markers/callbacks, donated carry,
            # rounds scanned inside, psum lowered to all-reduce, the peer
            # gather present iff the method gathers, nonzero flops
            report = lint_hlo.assert_round_block(
                lowered, expect_collectives=True, expect_gather=wants_gather)
            assert report.has_scan_loop and report.donated, method
        print("SHARDED_EXEC_OK")
    """)
    assert "SHARDED_EXEC_OK" in out


def test_sharded_single_device_matches_fused():
    """D=1 degenerates to the fused engine (collectives become identities)
    — cheap in-process parity check on the default one-device CPU."""
    model_cfg, train, test, pm, p_err = _tiny_setup()
    fused = FederatedSimulation(model_cfg, train, test, pm, p_err,
                                _cfg())
    sharded = FederatedSimulation(model_cfg, train, test, pm, p_err,
                                  _cfg(sharded=True, shard_devices=1))
    hf, hs = fused.run("pfedwn"), sharded.run("pfedwn")
    np.testing.assert_allclose(hs["target_acc"], hf["target_acc"], atol=5e-3)
    np.testing.assert_allclose(np.stack(hs["pi"]), np.stack(hf["pi"]),
                               atol=1e-4)
    assert sharded.last_run_stats["engine"] == "sharded"
    assert sharded.last_run_stats["device_calls"] == 2    # blocks [1, 2]


def test_sharded_mesh_validation_errors():
    model_cfg, train, test, pm, p_err = _tiny_setup(n_clients=3)
    sim = FederatedSimulation(model_cfg, train, test, pm, p_err,
                              _cfg(sharded=True, shard_devices=2))
    with pytest.raises(ValueError, match="divisible"):
        sim.placement()
    # a mesh wider than the visible devices (D chosen to divide N so the
    # divisibility check can't mask the device-count error)
    import jax
    d = len(jax.devices()) + 1
    model_cfg, train, test, pm, p_err = _tiny_setup(n_clients=2 * d)
    sim2 = FederatedSimulation(model_cfg, train, test, pm, p_err,
                               _cfg(sharded=True, shard_devices=d))
    with pytest.raises(ValueError, match="devices"):
        sim2.placement()
