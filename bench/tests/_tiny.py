"""Tiny configurations and cells for the benchmark's CPU tests."""
import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common  # noqa: E402

DENSE = dict(name="tiny-dense", family="dense", source="test",
             num_hidden_layers=2, hidden_size=64, intermediate_size=128,
             vocab_size=256, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, rope_theta=1e6, tie_word_embeddings=True,
             rms_norm_eps=1e-6, hidden_act="silu")
SSM = dict(name="tiny-ssm", family="ssm", source="test", n_layer=2,
           d_model=64, d_intermediate=0, vocab_size=256, d_state=16,
           d_conv=4, expand=2, headdim=16, ngroups=1, chunk_size=16,
           norm_epsilon=1e-5, tie_embeddings=True)


def serve_cell(limit: float = 0.008) -> common.Cell:
    """The serving cell's kind, traffic and layout at a size a CPU test
    run holds.  The limit lies between the program's widest logit gap at
    this size (0 to 0.0019 over eight seeds) and the fp8 control's
    (0.025 to 0.111 over the same seeds)."""
    bm = common.read_json(ROOT, "BENCHMARK.json")
    entry = bm["workloads"][0]
    traffic = copy.deepcopy(common.read_json(
        common.BENCH, "traffic", f"{entry['traffic']}.json"))
    traffic.update(prompt_len=16, rate_per_s=10.0,
                   output_len={"median": 4, "sigma": 0.5, "min": 2,
                               "max": 8})
    spec = copy.deepcopy(common.read_json(
        common.BENCH, "workloads", f"{entry['name']}.json"))
    spec.update(slots=2, max_seq=64, sample_requests=3, trace_seconds=1,
                limits={"logit_gap": limit})
    return common.Cell("tiny.serve", 1, spec, dict(DENSE), traffic, [], [])
