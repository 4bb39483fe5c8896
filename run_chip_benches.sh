#!/bin/sh
# One-shot chip benchmark dossier (VERDICT r3 item 1): run on a host with
# the real TPU chip reachable. Produces the committed sweep artifacts:
#   bench_headline.json    — BERT-large seq128 samples/s/chip (the driver
#                            metric; BASELINE.md row 3)
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

echo "== pipeline schedule sweep (virtual CPU mesh) =="
JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    BENCH_PP_SWEEP=1 BENCH_OUT=bench_pp_sweep.json python bench.py

echo "artifacts written; commit bench_headline.json bench_pp_sweep.json"
