"""A benchmark root at a size a CPU test run can hold: the real benchmark's
files, plus configurations cut from the real ones (the same key mappings and
training settings at a few dozen wide), their traffic and their limits, and
a model family that only the test tree holds."""
import json
import os
import shutil

from bench import harness

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
# CPU readings of the tiny cells over 8 seeds (the bf16 program against the
# float32 reference): loss_gap up to 3e-4, grad_gap up to 4.6e-3, change_gap
# up to 1.4e-2; the fp8 control reads grad_gap 9.7e-3 and more, half a batch
# grad_gap 0.21 and more, a state left unchanged change_gap 1
LIMITS = {"loss_gap": 2e-3, "grad_gap": 8e-3, "change_gap": 0.5}
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
CPU = dict(require_chip=False, peak=PEAK, device_plane="/host:CPU",
           ops_line="tf_XLA", cache=False)

SHRINK = {
    "qwen2-1.5b": {"num_hidden_layers": 4, "hidden_size": 64,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "intermediate_size": 128, "vocab_size": 256},
    "chatglm3-6b": {"num_layers": 3, "hidden_size": 64,
                    "num_attention_heads": 4, "multi_query_group_num": 2,
                    "kv_channels": 16, "ffn_hidden_size": 96,
                    "padded_vocab_size": 384},
}
# tiny configuration: (the real one it is cut from, its stages)
CONFIGS = {"tiny-qwen": ("qwen2-1.5b", 2), "tiny-glm": ("chatglm3-6b", 3),
           "tiny-qwen4": ("qwen2-1.5b", 4)}
# (tiny cell, configuration, traffic, stage, the real cell it stands for)
CELLS = (("tiny-qwen.stage0_sil", "tiny-qwen", "tiny_stage0", 0,
          "qwen2-1.5b.stage0_sil"),
         ("tiny-qwen.stage_last_ce", "tiny-qwen", "tiny_last", -1,
          "qwen2-1.5b.stage_last_ce"),
         ("tiny-glm.stage_sil", "tiny-glm", "tiny_stage1", 1,
          "chatglm3-6b.stage_sil"))
# every stage at once, one per chip: a run needs 4 devices
DEPLOYMENT = ("tiny-qwen4.fig5", "tiny-qwen4", "tiny_all", "all",
              "qwen2-1.5b.fig5_4chip")
TEST_FAMILY = os.path.join(os.path.dirname(__file__), "probe_family.py")


def write_limits(root: str, cell: str, prefixes=("",), limits=None):
    with open(os.path.join(root, "bench/limits", cell + ".json"), "w") as f:
        json.dump({p + n: {"limit": (limits or LIMITS)[n]}
                   for p in prefixes for n in NUMBERS}, f)


def make_root(dst: str, limits=None) -> str:
    """Write a benchmark root under ``dst`` and return it."""
    shutil.copytree(os.path.join(harness.ROOT, "bench"),
                    os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(TEST_FAMILY, os.path.join(dst, "bench/families/probe.py"))
    bench = harness.load_benchmark(harness.ROOT)
    configs = []
    for name, (real, stages) in CONFIGS.items():
        entry = harness.config_entry(bench, real)
        with open(os.path.join(harness.ROOT, entry["file"])) as f:
            raw = json.load(f)
        raw.update(SHRINK[real], name=name)
        raw["deployment"]["stages"] = stages
        path = f"bench/configs/{name}.json"
        with open(os.path.join(dst, path), "w") as f:
            json.dump(raw, f)
        configs.append(dict(entry, name=name, file=path))
    bench["configs"] = configs
    bench["workloads"] = []
    for cell, config, traffic, stage, _ in CELLS + (DEPLOYMENT,):
        with open(os.path.join(dst, "bench/traffic", traffic + ".json"),
                  "w") as f:
            json.dump({"stage": stage, "batch": 2, "seq": 64, "pool": 8,
                       "branch": 4, "repeat_p": 0.1, "span": 8,
                       "check_steps": 3}, f)
        stages = CONFIGS[config][1]
        write_limits(dst, cell, [f"s{k}." for k in range(stages)]
                     if stage == "all" else [""], limits)
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic,
                                   "chips": stages if stage == "all" else 1,
                                   "why": "a CPU test at a tiny size"})
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [c[0] for c in CELLS + (DEPLOYMENT,)
                              if c[4] in m["workloads"]]
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst
