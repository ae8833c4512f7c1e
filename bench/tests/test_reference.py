"""The plain reference against the program at a small size on the CPU.

With every token selected (retention 1), Salca's selection keeps the whole
context and the float32 program must agree with the float32 reference up to
summation order: this pins the reference's model (norms, RoPE, GQA, int8
rows, heads, prefill vs decode) to what the program serves. At the
configured 5% retention, over a context where the selection keeps 128 of
900 tokens, the float32 program with its float32 score chain must agree
with the reference too: this pins the reference's selection (feature codes,
scores, bins, max-pool, threshold, cap). The program's default score chain
rounds every step to bf16, which flips tokens at the threshold bin. At the
tiny cell's own settings, the fp8 control must come out not correct where
the bf16 program comes out correct (at the cell's own size that is read on
the chip, by bench/control.py).
"""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402  (sets JAX_PLATFORMS=cpu before jax loads)
from harness import check, program, weights  # noqa: E402

program.import_program()


def _reference():
    import importlib.util
    path = tiny.BENCH / "references" / "qwen3_salca.py"
    spec = importlib.util.spec_from_file_location("qwen3_salca", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# an engine whose contexts are long enough that 5% retention selects
LONG = {"slots": 2, "max_seq": 1024, "num_blocks": 160, "prefill_chunk": 128}


def _serve(config, seed, new_tokens=24, engine_shape=None, prompt_len=176):
    """One request through the engine; the logits row behind every decode
    token, and the request."""
    from repro.runtime.serve import Request
    cfg = program.model_config(config)
    params = weights.make_params(program.param_shapes(cfg), seed)
    engine = program.build_engine(cfg, params,
                                  engine_shape or tiny.TINY_TRAFFIC["engine"],
                                  config["serving"])
    rows, tick = {}, engine._decode

    def record(p, s, t, a):
        out = tick(p, s, t, a)
        for slot, req in engine._active.items():
            rows[len(req.output)] = np.asarray(out[1][slot], np.float64)
        return out
    engine._decode = record
    prompt = np.random.default_rng(seed).integers(0, 512, prompt_len).astype(np.int32)
    req = Request(rid=0, prompt=prompt, max_new_tokens=new_tokens)
    engine.submit(req)
    engine.run()
    return params, req, rows


def _config(dtype, retention):
    c = tiny.tiny_config()
    c["program"]["set"].update(dtype=dtype, salca_retention=retention)
    c["serving"].update(dtype=dtype, salca_retention=retention)
    return c


def test_f32_program_matches_the_reference_when_all_tokens_are_selected():
    c = _config("float32", 1.0)
    params, req, rows = _serve(c, seed=3)
    ref = np.asarray(_reference().logits(params, c, 256, req.prompt, req.output))
    assert ref.shape == (len(req.output), 512)
    worst = max(np.abs(rows[i][:512] - ref[i]).max() for i in range(1, len(req.output)))
    assert worst < 2e-4
    assert check.gaps(ref, req.output).max() == pytest.approx(0.0, abs=1e-4)


@pytest.fixture
def f32_score_chain():
    from repro.flags import PERF, set_flags
    was = PERF.bf16_collectives
    set_flags(bf16_collectives=False)
    yield
    set_flags(bf16_collectives=was)


@pytest.mark.parametrize("seed", [3, 4])
def test_f32_program_matches_the_reference_at_five_percent_retention(
        seed, f32_score_chain):
    c = _config("float32", 0.05)
    ref = _reference()
    k = dict(ref.salca_sizes(c, LONG["max_seq"]))["k"]
    assert k == 128 < 900                 # the selection drops most tokens
    params, req, rows = _serve(c, seed, new_tokens=32, engine_shape=LONG,
                               prompt_len=900)
    lg = np.asarray(ref.logits(params, c, LONG["max_seq"], req.prompt, req.output))
    worst = max(np.abs(rows[i][:512] - lg[i]).max() for i in range(1, len(req.output)))
    assert worst < 5e-4
    assert check.gaps(lg, req.output).max() == pytest.approx(0.0, abs=1e-4)


def test_bf16_score_chain_flips_threshold_tokens_at_five_percent():
    """The program's default score chain, at float32 everywhere else: the
    rows drift from the reference by what flipped threshold tokens carry."""
    c = _config("float32", 0.05)
    params, req, rows = _serve(c, 3, new_tokens=32, engine_shape=LONG,
                               prompt_len=900)
    lg = np.asarray(_reference().logits(params, c, LONG["max_seq"], req.prompt,
                                        req.output))
    worst = max(np.abs(rows[i][:512] - lg[i]).max() for i in range(1, len(req.output)))
    assert 1e-3 < worst < 1.0


@pytest.mark.parametrize("seed", [4, 5, 6])
def test_fp8_control_comes_out_not_correct(seed):
    """The control at the tiny cell's own settings (bf16, 5% retention): the
    reference in fp8 in the program's place fails the tiny cell's check,
    which the bf16 program passes."""
    c = tiny.tiny_config()
    params, req, _ = _serve(c, seed=seed, new_tokens=48)
    sound, control = check.readings(_reference(), params, c, 256, [req])
    assert check.verdict(sound, c["check"], 0)[0] is True
    assert check.verdict(control, c["check"], 0)[0] is False
