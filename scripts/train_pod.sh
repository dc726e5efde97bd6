#!/usr/bin/env bash
# Pod-scale 7B launcher (round 23): supervisor-fronted rows of the
# gpt2_7b recipe grid — pp (interleaved-1F1B) x fsdp x fsdp_tp —
# timeout-capped legs, tee'd logs,
# one timestamped capture dir).
#
# There is no torchrun on TPU, and since round 13 there is no bare
# worker either: the elastic supervisor (train/supervisor.py) spawns one
# worker per host slot, wires the JAX_* rendezvous env (fresh
# coordinator port per gang incarnation), and survives a mid-run host
# loss by gang-restarting from the last verified checkpoint.
#
# Two kinds of rows, because ZeRO-Offload (train/offload.py) is
# single-controller — the host update needs ONE process owning the whole
# mesh, so it applies on a v5e-8 (one host, 8 chips) and not across a
# DCN gang (resolve_offload fails loudly on OFFLOAD=on multi-process):
#   pp, fsdp  — single-controller v5e-8 rungs, OFFLOAD=on: the only way
#               7B prices under 16 GiB/chip on 8 chips (memplan:
#               fsdp 15.60 DNF -> 12.09 offloaded; pp pipe=8 17.81 DNF
#               -> 12.75 offloaded)
#   fsdp_tp   — the multi-host scale-out row, HOSTS x 4 chips, in-HBM
#               moments: capacity comes from more chips (12.75 GiB at
#               16 devices without offload)
# Run on the coordinator node:
#     bash scripts/train_pod.sh                      # all rows, HOSTS=4
#     ROWS=fsdp_tp HOSTS=8 bash scripts/train_pod.sh # one row, bigger gang
# CPU bring-up (no TPU attached): CPU_DEVICES=1 PLATFORM=cpu and the
# same command drives the 2-process smoke CI runs under tier1.yml.
#
# Each row is gated by its memplan pricing first — at the same mesh axes
# and offload mode the worker will actually use — so a row that fails
# the plan is skipped loudly instead of discovered 40 minutes into
# compile.
set -uo pipefail
cd "$(dirname "$0")/.."
mkdir -p pod_capture
TS=$(date -u +%m%d_%H%M)

HOSTS="${HOSTS:-4}"
ROWS="${ROWS:-pp fsdp fsdp_tp}"
PLATFORM="${PLATFORM:-auto}"
CPU_DEVICES="${CPU_DEVICES:-0}"
MAX_ITERS="${MAX_ITERS:-20000}"
LEG_TIMEOUT="${LEG_TIMEOUT:-14400}"

# shared 7B worker argv: preset seeds the model block; 2**19 tokens/step,
# micro-batch 1/device with block remat (memplan's fit point for
# 16 GiB/chip)
COMMON=(--preset gpt2_7b
    --dataset tinystories
    --platform "$PLATFORM"
    --total_batch_size_str "2**19"
    --batch_size 1
    --max_iters "$MAX_ITERS"
    --learning_rate 3e-4 --warmup_steps 2000
    --ckpt_interval 1000
    --act_recomp --act_recomp_policy block
    --eval --eval_interval 500 --eval_iters 10)

echo "[train_pod] 7B rung at $TS: rows='$ROWS' hosts=$HOSTS" \
    | tee "pod_capture/pod_${TS}.txt"

for ROW in $ROWS; do
    # pp runs pipe=8: at pipe=4 the per-stage fp32 grad accumulators
    # (not dp-sharded under pp) overshoot 16 GiB/chip by ~1 GiB even
    # with the moments offloaded — memplan prices 16.05 vs 12.75 GiB.
    case "$ROW" in
        pp)      FLAGS=(--parallelism pp --pp_size 8 --pp_schedule 1f1b)
                 PLAN=(--pp-size 8 --offload)
                 ROW_HOSTS=1 ROW_DEVS=8 ROW_OFFLOAD=on ;;
        fsdp)    FLAGS=(--parallelism fsdp)
                 PLAN=(--offload)
                 ROW_HOSTS=1 ROW_DEVS=8 ROW_OFFLOAD=on ;;
        fsdp_tp) FLAGS=(--parallelism fsdp_tp --tp_size 4)
                 PLAN=(--tp-size 4)
                 ROW_HOSTS=$HOSTS ROW_DEVS=$((HOSTS * 4)) ROW_OFFLOAD=auto ;;
        *) echo "[train_pod] unknown row '$ROW' (pp|fsdp|fsdp_tp)"; exit 2 ;;
    esac
    RUN="gpt2_7b_${ROW}"

    # 1) price the row before burning the reservation (rc=1 -> skip);
    #    the gate sees the same mesh axes and offload mode the worker
    #    will use
    if ! python -m distributed_pytorch_tpu.train.memplan \
            --preset gpt2_7b --recipe "$ROW" --devices "$ROW_DEVS" \
            ${PLAN[@]+"${PLAN[@]}"} \
            2>&1 | tee "pod_capture/memplan_${ROW}_${TS}.log"
    then
        echo "[train_pod] row $ROW does not price under HBM — skipped"
        continue
    fi

    # 2) the supervised run: gang of $ROW_HOSTS workers, elastic restart
    #    on host loss, AOT prewarm skipped automatically under offload
    SUP=(python -m distributed_pytorch_tpu.train.supervisor
        --hosts "$ROW_HOSTS" --run-name "$RUN")
    [ "$CPU_DEVICES" -gt 0 ] && SUP+=(--cpu-devices "$CPU_DEVICES")
    CMD=(env OFFLOAD="$ROW_OFFLOAD"
        "${SUP[@]}" -- "${COMMON[@]}" "${FLAGS[@]}" --file_name "$RUN")
    echo "+ ${CMD[*]}" | tee -a "pod_capture/pod_${TS}.txt"
    timeout "$LEG_TIMEOUT" "${CMD[@]}" \
        2>&1 | tee "pod_capture/${RUN}_${TS}.log"
    echo "[train_pod] row $ROW rc=$? -> pod_capture/${RUN}_${TS}.log" \
        | tee -a "pod_capture/pod_${TS}.txt"
done
echo "[train_pod] capture complete: pod_capture/pod_${TS}.txt"
