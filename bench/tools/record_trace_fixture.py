"""Record the small profiler trace that tests/bench/test_bench_trace.py
reduces: a smoke-size qwen3 engine serving one wave of 4 requests through
`harness.serve_window`, so the trace holds the benchmark's own spans, the
device planes and the program names of a real run.

    python3 bench/tools/record_trace_fixture.py OUT_DIR

Run it on a chip; it copies the `.xplane.pb` to OUT_DIR/tick_trace.xplane.pb.
The committed copy is tests/bench/fixtures/tick_trace.xplane.pb.
"""
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

SMOKE = {
    "name": "qwen3-smoke", "program": "qwen3-1.7b", "reference": "dense",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 256, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True, "attention_bias": False, "qk_norm": True,
    "torch_dtype": "bfloat16"}
TRAFFIC = {"engine": "precise", "slots": 4, "prompt_len": 16,
           "max_len": 32, "classes": ["default"],
           "output": {"dist": "lognormal", "median": 6, "sigma": 0.6,
                      "min": 3, "max": 12}}


def main(out: str) -> int:
    from bench import cells, harness, traffic
    cfg = cells.program_config(SMOKE, approx=False)
    params = cells.family(SMOKE).program_params(0, SMOKE,
                                                cfg.padded_vocab_size)
    engine = harness.build_engine(ROOT, SMOKE, TRAFFIC, params)
    engine.warmup()
    waves = traffic.waves(TRAFFIC, 0, SMOKE["vocab_size"])
    tmp = tempfile.mkdtemp()
    # a 50 ms window: an admission and a few ticks are traced
    harness.serve_window(engine, waves, 0.05, trace_dir=tmp)
    os.makedirs(out, exist_ok=True)
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                    recursive=True)[0]
    dst = os.path.join(out, "tick_trace.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print(dst, os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
