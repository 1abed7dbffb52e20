#!/bin/bash
# Real-data convergence soak with hard-kill resume (VERDICT r4 #5).
#
# Runs bigdl_tpu.examples.convergence_docs_corpus in segments; every
# other segment is kill -9'd at a random point mid-training, and the
# next segment must resume from the last committed Orbax step (the
# example logs `resumed_from` into LONGRUN_CONVERGENCE.jsonl).  Runs
# until TARGET_MIN minutes of wall clock have elapsed.
#
#   TARGET_MIN=75 bash tools/convergence_run.sh
set -u
cd /root/repo
TARGET_MIN=${TARGET_MIN:-75}
SEG_ITERS=${SEG_ITERS:-150}
CKPT=${CKPT:-}   # empty: the example picks dialect-specific defaults
LOG=${LOG:-}
EXTRA_FLAGS=${EXTRA_FLAGS:-}   # e.g. --llama
export JAX_PLATFORMS=cpu
export XLA_FLAGS=--xla_force_host_platform_device_count=8

start=$(date +%s)
seg=0
kills=0
while [ $(( $(date +%s) - start )) -lt $(( TARGET_MIN * 60 )) ]; do
    seg=$((seg + 1))
    python -m bigdl_tpu.examples.convergence_docs_corpus \
        --iters "$SEG_ITERS" \
        ${CKPT:+--ckpt-dir "$CKPT"} ${LOG:+--log "$LOG"} \
        $EXTRA_FLAGS > "/tmp/convergence_seg${seg}.log" 2>&1 &
    pid=$!
    if [ $((seg % 2)) -eq 0 ]; then
        # hard-kill mid-training: past compile (~60s), before the end
        sleep $(( 70 + RANDOM % 60 ))
        if kill -9 "$pid" 2>/dev/null; then
            kills=$((kills + 1))
            echo "$(date -u +%FT%TZ) segment $seg KILLED (-9)" \
                >> /tmp/convergence_run.log
        fi
        wait "$pid" 2>/dev/null
    else
        wait "$pid"
        rc=$?  # capture BEFORE the $(date) substitution resets $?
        echo "$(date -u +%FT%TZ) segment $seg completed rc=$rc" \
            >> /tmp/convergence_run.log
    fi
done
echo "$(date -u +%FT%TZ) DONE: $seg segments, $kills hard kills, " \
     "$(( ($(date +%s) - start) / 60 )) min" >> /tmp/convergence_run.log
