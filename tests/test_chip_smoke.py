"""chip_smoke.py's phases at smoke size on the CPU.

The script itself refuses to run off the chip; these tests drive its
phase functions on a smoke-sized olmo-1b so a change that breaks the
script's control flow fails here, not on the chip.  Kernels run in
interpret mode; the decode-program check needs the chip and is not
exercised.
"""
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro import configs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke as cs  # noqa: E402

RUN = cs.Run(n_slots=4, max_seq=128, n_requests=6, max_prompt=64,
             new_tokens=8, prompt_bucket=32, page_size=16, n_checked=2,
             check_steps=4)


@pytest.fixture(scope="module")
def served():
    cfg = configs.get_config("olmo-1b").smoke()
    params = cs.init_model(cfg, RUN.seed)
    mixed = cs.select_policy(cfg, params)
    engine = cs.new_engine(cfg, cs.pack(cfg, params, mixed), mixed, RUN)
    requests = cs.make_requests(cfg, RUN)
    _, done = cs.serve(engine, requests, RUN)
    return cfg, params, engine, requests, done


def test_serves_every_request(served):
    _, _, _, requests, done = served
    assert len({len(r.prompt) for r in requests}) > 1      # unequal prompts
    assert max(len(r.prompt) for r in requests) == RUN.max_prompt
    assert all(len(done[r.uid].tokens) == RUN.new_tokens for r in requests)


def test_logits_within_bound_of_f32_reference(served):
    cfg, params, engine, requests, _ = served
    cs.check_against_reference(engine, cfg, params,
                               requests[:RUN.n_checked], RUN)


def test_reference_check_rejects_other_weights(served):
    """The gate has teeth: scoring against a different model's reference
    fails it."""
    cfg, _, engine, requests, _ = served
    other = cs.init_model(cfg, RUN.seed + 1)
    with pytest.raises(cs.CheckFailed):
        cs.check_against_reference(engine, cfg, other,
                                   requests[:RUN.n_checked], RUN)


def test_served_tokens_score_high_under_reference(served):
    cfg, params, engine, requests, done = served
    ref = cs.check_served_tokens("smoke", cfg, params, engine.policy_arrays,
                                 requests[:RUN.n_checked], done, RUN)
    assert ref.shape == (RUN.n_checked, cfg.vocab)


def test_served_token_check_rejects_unrelated_tokens(served):
    """Tokens the reference does not favour fail the score bound."""
    import dataclasses
    import numpy as np
    cfg, params, engine, requests, done = served
    rng = np.random.default_rng(7)
    noise = {uid: dataclasses.replace(
        c, tokens=rng.integers(0, cfg.vocab, len(c.tokens)).tolist())
        for uid, c in done.items()}
    with pytest.raises(cs.CheckFailed):
        cs.check_served_tokens("noise", cfg, params, engine.policy_arrays,
                               requests[:RUN.n_checked], noise, RUN)


def test_kernel_checks_interpret():
    cs.check_kernels(RUN, qmm_shapes=((256, 128), (512, 256)), heads=2,
                     head_dim=32, impl="interpret")


def _script(args, cwd, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    e.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          capture_output=True, text=True, timeout=300, env=e)


def test_refuses_without_a_tpu():
    out = _script([], ROOT)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = _script([], str(tmp_path))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_four_device_phase_on_virtual_devices():
    """--chips 4's phase over 4 virtual CPU devices: the mesh engine's
    params and page pools spread over all four, and its logits and tokens
    match the one-device engine."""
    code = f"""
import sys
sys.path.insert(0, {ROOT!r})
import jax
import chip_smoke as cs
from repro import configs
cfg = configs.get_config("olmo-1b").smoke()
cs.run_four_chips(cfg, cs.{RUN!r}, cs.Phases(jax))
print("OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=560, env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "6 of 6 requests identical" in out.stdout


def test_compile_events_are_counted():
    phase = cs.Phases(jax)
    with phase("one jit"):
        jax.jit(lambda x: x * 2)(jax.numpy.ones(3)).block_until_ready()
    assert phase.compile_s > 0
