import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimb driver: for the three chosen cells, run the baseline +
each candidate change, recording the roofline terms per variant.  Results
land in experiments/dryrun/*.json (tagged) and a summary TSV here.

Chosen cells (EXPERIMENTS.md §Perf):
  smollm-360m  × train_4k — worst baseline roofline fraction (0.0028)
  mixtral-8x7b × train_4k — most collective-bound (x = 79 s baseline)
  llama3-8b    × train_4k — canonical dense-LM cell (the shape the
                            framework's train path is built around)
"""

import json
import pathlib
import sys
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.launch.dryrun import run_cell  # noqa: E402

OUT = pathlib.Path(__file__).parent / "hillclimb_results.tsv"

# (arch, shape, tag, overrides, hypothesis)
RUNS = [
    # ---- llama3-8b train_4k ------------------------------------------------
    ("llama3-8b", "train_4k", "hc-base", {},
     "baseline: FSDP+TP, micro=8, qblocks=1"),
    ("llama3-8b", "train_4k", "hc-qb4", {"attn_qblocks": 4},
     "causal chunk skip: attention flops ~62.5% -> compute term down"),
    ("llama3-8b", "train_4k", "hc-zero1", {"rules": "zero1"},
     "ZeRO-1: TP params + FSDP moments -> fewer gathers"),
    ("llama3-8b", "train_4k", "hc-micro4", {"microbatches": 4},
     "fewer micros: per-micro TP all-reduce count halves"),
    ("llama3-8b", "train_4k", "hc-dp", {"rules": "dp", "microbatches": 1},
     "pure DP/FSDP over all 256 chips: NO TP activation all-reduces; "
     "collectives = 1 param gather + 1 grad reduce-scatter per step"),
    ("llama3-8b", "train_4k", "hc-best",
     {"rules": "dp", "microbatches": 1, "attn_qblocks": 4},
     "combine dp remap with causal chunk skip"),
    # ---- mixtral-8x7b train_4k ---------------------------------------------
    ("mixtral-8x7b", "train_4k", "hc-base", {},
     "baseline MoE: EP-fallback TP + FSDP"),
    ("mixtral-8x7b", "train_4k", "hc-qb4", {"attn_qblocks": 4},
     "causal chunk skip on the SWA layers"),
    ("mixtral-8x7b", "train_4k", "hc-dp", {"rules": "dp", "microbatches": 2},
     "pure DP/FSDP: experts local, no dispatch resharding collectives"),
    ("mixtral-8x7b", "train_4k", "hc-best",
     {"rules": "dp", "microbatches": 2, "attn_qblocks": 4},
     "combined"),
    # ---- smollm-360m train_4k ----------------------------------------------
    ("smollm-360m", "train_4k", "hc-base", {},
     "baseline: heads replicated (15 vs 16-way model axis)"),
    ("smollm-360m", "train_4k", "hc-qb4", {"attn_qblocks": 4},
     "causal chunk skip: attention dominates this tiny model"),
    ("smollm-360m", "train_4k", "hc-qb8", {"attn_qblocks": 8},
     "deeper skip: (Q+1)/2Q -> 56%"),
    ("smollm-360m", "train_4k", "hc-dp", {"rules": "dp", "microbatches": 1},
     "pure DP: kills the 15-head replication waste entirely "
     "(per-device attention work /16)"),
    ("smollm-360m", "train_4k", "hc-best",
     {"rules": "dp", "microbatches": 1, "attn_qblocks": 8},
     "combined"),
]


def main():
    rows = []
    for arch, shape, tag, overrides, hyp in RUNS:
        try:
            r = run_cell(arch, shape, False, tag=tag,
                         overrides=dict(overrides), verbose=True)
            rl = r["roofline"]
            rows.append((arch, shape, tag, hyp, rl))
            print(f"== {arch} {tag}: c={rl['compute_s']:.3f} "
                  f"m={rl['memory_s']:.3f} x={rl['collective_s']:.3f} "
                  f"dom={rl['dominant']} frac={rl['roofline_fraction']:.4f} "
                  f"fits={rl['fits_hbm']}", flush=True)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            rows.append((arch, shape, tag, hyp, None))
        with OUT.open("w") as f:
            f.write("arch\tshape\ttag\thypothesis\tcompute_s\tmemory_s\t"
                    "collective_s\tdominant\tfraction\tfits\n")
            for a, s, t, h, rl in rows:
                if rl is None:
                    f.write(f"{a}\t{s}\t{t}\t{h}\tFAIL\n")
                else:
                    f.write(f"{a}\t{s}\t{t}\t{h}\t{rl['compute_s']:.4f}\t"
                            f"{rl['memory_s']:.4f}\t{rl['collective_s']:.4f}"
                            f"\t{rl['dominant']}\t"
                            f"{rl['roofline_fraction']:.4f}\t"
                            f"{rl['fits_hbm']}\n")


if __name__ == "__main__":
    main()
