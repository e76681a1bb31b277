#!/bin/sh
# Refresh every bench number sequentially: one `python bench.py` child at a
# time, because a chip belongs to one process (this shell never touches JAX)
# and concurrent runs would corrupt the measurements. The accelerator legs
# fail without an accelerator; there is no CPU fallback.
# Usage: sh scripts/run_all_benches.sh [out_file]
out="${1:-BENCH_ALL.jsonl}"
errdir=$(mktemp -d)
echo "bench stderr in $errdir" >&2
: > "$out"
failed=0
for w in ppo a2c sac dreamer_v1 dreamer_v2 dreamer_v3 dreamer_v3_S; do
    echo "=== $w ===" >&2
    # Harvest the last JSON line specifically (grep '^{'): even with stderr
    # split off, a library printing to stdout must not corrupt the record.
    line=$(python bench.py "$w" 2>"$errdir/$w.err" | grep '^{' | tail -1)
    if [ -n "$line" ]; then
        echo "$line" | tee -a "$out"
    else
        echo "WARNING: $w produced no result — stderr:" >&2
        tail -5 "$errdir/$w.err" >&2
        failed=1
    fi
done
# keep stderr only when something failed (post-mortem); clean otherwise
[ "$failed" = 0 ] && rm -rf "$errdir"
