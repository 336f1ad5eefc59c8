"""chip_smoke.py's phase functions, driven tiny on the CPU mesh.

`main()` refuses to run without a TPU; its phases take their sizes as
arguments, so the same code that proves the chip path runs here at
`LlamaConfig.tiny()` with the Pallas kernels in interpret mode.  What this
cannot see — Mosaic, HBM, bf16 rounding — is the chip run's business.
"""

import os
import subprocess
import sys

import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import mesh as pmesh
from paddle_tpu.ops import flash_attention as fa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

# LlamaConfig.tiny() cut to one layer: every engine step is traced through
# the Pallas interpreter, and this module sits inside tier-1's time cap
TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=4,
    max_position_embeddings=256,
)
# page size 8: a 12-token shared prefix fills one page, 14- and 15-token
# prompts generate across the 16-token page boundary
SERVE_SIZES = dict(
    slots=2, max_len=64, buckets=[32], page_size=8, shared_prefix=12,
    shared_suffixes=[2, 3], lone_lengths=[10, 20], new_tokens=6,
    first_k=4, min_agree=1.0, kernel_tol=1e-4, kernel_tol_int8=1e-4,
)
TRAIN_SIZES = dict(batch=2, seqlen=128, steps=3)


@pytest.fixture(scope="module", autouse=True)
def _isolated():
    """Interpret-mode kernels for the whole module; leave no mesh and no
    RNG-stream shift behind for the modules that run after this one."""
    rng = paddle.get_rng_state()
    mesh = pmesh.get_mesh()
    prev, fa._FORCE_INTERPRET = fa._FORCE_INTERPRET, True
    yield
    fa._FORCE_INTERPRET = prev
    pmesh.set_mesh(mesh)
    paddle.set_rng_state(rng)


@pytest.fixture(scope="module")
def one_chip():
    return chip_smoke.serve_phase(TINY, amp=False, **SERVE_SIZES)


def test_serve_phase_tiny(one_chip):
    assert len(one_chip["tokens"]) == 4
    assert all(len(t) == SERVE_SIZES["new_tokens"] for t in one_chip["tokens"])


def test_train_phase_tiny():
    out = chip_smoke.train_phase(TINY, amp=False, **TRAIN_SIZES)
    assert len(out["losses"]) == TRAIN_SIZES["steps"]


def test_latent_serve_phase_tiny():
    from paddle_tpu.models import DeepseekV32Config

    config = {k: v for k, v in vars(DeepseekV32Config.tiny(experts_held=4)).items() if k != "dtype"}
    tokens = chip_smoke.latent_serve_phase(
        config, dtype="float32", slots=2, max_len=128, buckets=[16, 32], lengths=[20, 75],
        new_tokens=5, page_size=8)
    assert [len(t) for t in tokens] == [5, 5]


def test_cache_phase_names_a_directory():
    assert chip_smoke.cache_phase()["dir"]


def test_four_chip_phase_tiny(one_chip):
    # the CPU mesh has 8 devices: tp=4 serving, then dp=4 x mp=2 training
    chip_smoke.four_chip_phase(
        TINY, TINY, one_chip["tokens"], amp=False, serve_sizes=SERVE_SIZES,
        train_sizes=dict(TRAIN_SIZES, batch=4, steps=2), tp=4,
        hybrid={"dp": 4, "mp": 2},
    )


def test_a_failed_check_raises():
    with pytest.raises(AssertionError, match="max abs err"):
        chip_smoke.kernel_vs_oracle(
            heads=4, kv_heads=4, head_dim=16, page_size=8, slots=2,
            max_len=32, dtype="float32", tol=0.0, quant=True,
        )


def test_main_refuses_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    assert '"ok"' not in out


def test_router_and_autoscaler_process_stays_off_jax():
    """A chip belongs to one process: the process that spawns the replicas
    must reach a running Autoscaler without initialising a jax backend."""
    code = (
        "from jax._src import xla_bridge as xb\n"
        "from paddle_tpu.serving import Autoscaler, Router\n"
        "router = Router([], probe_interval=3600)\n"
        "Autoscaler(router, devices_total=4, min_replicas=1, max_replicas=2)\n"
        "assert not xb._backends, list(xb._backends)\n"
        "try:\n"
        "    Autoscaler(router, min_replicas=1, max_replicas=2)\n"
        "except ValueError as e:\n"
        "    assert 'devices_total' in str(e)\n"
        "else:\n"
        "    raise SystemExit('devices_total was not required')\n"
        "assert not xb._backends, list(xb._backends)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
