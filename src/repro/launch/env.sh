# Fleet launch environment for serve_miner (source before launching).
#
# Allocator settings for multi-host runs: tcmalloc for the host-side bitset
# churn.
#
# Usage:
#   source launch/env.sh
#   python -m repro.launch.serve_miner --mesh 2x4x1 \
#     --coordinator-address host0:9911 --num-processes 2 --process-id $ID

# faster malloc for the append/itemize hot path; skip silently if absent
_TCMALLOC=/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4
if [ -f "$_TCMALLOC" ]; then
  export LD_PRELOAD="$_TCMALLOC"
fi
# no numpy large-alloc warnings on multi-GB bitset matrices
export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=60000000000
export TF_CPP_MIN_LOG_LEVEL=4
