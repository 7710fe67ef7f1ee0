#!/bin/sh
# Service selector: manager | scheduler | trainer | colocated | daemon | dfget | dfcache | dfstore
# (colocated = scheduler and trainer in one process, for a host with one chip)
set -e
svc="$1"; shift || true
case "$svc" in
  manager|scheduler|trainer|colocated) exec python -m "dragonfly2_tpu.$svc" "$@" ;;
  daemon)  exec python -m dragonfly2_tpu.client.daemon "$@" ;;
  dfget)   exec python -m dragonfly2_tpu.client.dfget "$@" ;;
  dfcache) exec python -m dragonfly2_tpu.client.dfcache "$@" ;;
  dfstore) exec python -m dragonfly2_tpu.client.dfstore "$@" ;;
  *) echo "usage: <manager|scheduler|trainer|colocated|daemon|dfget|dfcache|dfstore> [flags]" >&2; exit 2 ;;
esac
