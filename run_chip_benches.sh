#!/bin/sh
# One-shot chip benchmark dossier (VERDICT r3 item 1): run on a host with
# the real TPU chip reachable. Produces the committed sweep artifacts:
#   bench_headline.json    — BERT-large seq128 samples/s/chip (the driver
#                            metric; BASELINE.md row 3)
#   bench_attn_sweep.json  — streaming-kernel vs XLA ratio per seq length
#   bench_pp_sweep.json    — pipeline schedule sweep (gpipe vs 1f1b), run
#                            on the virtual CPU mesh (program structure)
# One bench.py process at a time: a chip belongs to one process.
set -e
cd "$(dirname "$0")"

echo "== headline (BERT-large seq128) =="
BENCH_OUT=bench_headline.json python bench.py

echo "== headline phase-2 (BERT-large seq512, streaming kernel auto) =="
BENCH_SEQ=512 BENCH_OUT=bench_headline_seq512.json python bench.py

echo "== recipe-faithful legs (256 samples/chip/step = 16K batch / 64"
echo "   chips — the WALLCLOCK.md projection inputs) =="
BENCH_BATCH=32 BENCH_GAS=8 BENCH_STEPS=16 \
    BENCH_OUT=bench_headline_recipe128.json python bench.py
BENCH_SEQ=512 BENCH_BATCH=8 BENCH_GAS=32 \
    BENCH_OUT=bench_headline_recipe512.json python bench.py

echo "== checkpoint save-stall (sync vs async writer) =="
BENCH_CKPT=1 BENCH_OUT=bench_ckpt.json python bench.py

echo "== MFU breakdown (engine-level ablations) =="
BENCH_MFU_BREAKDOWN=1 BENCH_OUT=bench_mfu_breakdown.json python bench.py

echo "== optimizer kernel microbench (pallas vs xla) =="
BENCH_OPT=1 BENCH_OUT=bench_opt.json python bench.py

echo "== real-data input path vs synthetic =="
BENCH_DATA=1 BENCH_OUT=bench_data.json python bench.py

echo "== attention kernel sweep =="
for SEQ in 128 512 1024 2048 4096; do
    BENCH_ATTN_SWEEP=1 BENCH_SEQ=$SEQ BENCH_OUT=bench_attn_seq${SEQ}.json \
        python bench.py
done
python - <<'EOF'
import json, os
rows = []
for seq in (128, 512, 1024, 2048, 4096):
    with open(f"bench_attn_seq{seq}.json") as f:
        rows.append(json.load(f))
    os.remove(f"bench_attn_seq{seq}.json")
with open("bench_attn_sweep.json", "w") as f:
    json.dump({"metric": "attention_kernel_speedup_by_seq",
               "unit": "x vs XLA path (kernel forced; auto dispatch "
                       "picks the better side per seq)", "rows": rows},
              f, indent=1)
print("wrote bench_attn_sweep.json")
EOF

echo "== pipeline schedule sweep (virtual CPU mesh) =="
JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    BENCH_PP_SWEEP=1 BENCH_OUT=bench_pp_sweep.json python bench.py

echo "artifacts written; commit bench_headline.json" \
     "bench_attn_sweep.json bench_pp_sweep.json"
