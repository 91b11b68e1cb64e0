#!/usr/bin/env bash
# swarm-tpu installer for TPU VMs and dev hosts (parity with the
# reference's install.sh venv bootstrap, /root/reference install.sh:1-232).
#
# Usage:  ./install.sh [--cpu]
#   --cpu   install the CPU jax backend (dev machines without a TPU)

set -euo pipefail

PYTHON=${PYTHON:-python3}
VENV_DIR=${VENV_DIR:-.venv}
BACKEND=tpu
[[ "${1:-}" == "--cpu" ]] && BACKEND=cpu

command -v "$PYTHON" >/dev/null || { echo "python3 not found"; exit 1; }
"$PYTHON" - <<'EOF' || { echo "python >= 3.10 required"; exit 1; }
import sys
sys.exit(0 if sys.version_info >= (3, 10) else 1)
EOF

echo "==> creating venv at $VENV_DIR"
"$PYTHON" -m venv "$VENV_DIR"
# shellcheck disable=SC1091
source "$VENV_DIR/bin/activate"
pip install --upgrade pip >/dev/null

echo "==> installing swarm-tpu ($BACKEND backend; deps from pyproject.toml)"
if [[ "$BACKEND" == "tpu" ]]; then
    pip install -e ".[tpu,test]" \
        -f https://storage.googleapis.com/jax-releases/libtpu_releases.html
else
    pip install -e ".[cpu,test]"
fi

echo "==> building native artifact codec"
python -c "from chiaswarm_tpu import native; print('native codec:', bool(native.load()))"

echo
echo "Done. Next steps:"
echo "  source $VENV_DIR/bin/activate"
echo "  python -m chiaswarm_tpu.cli init     # configure hive + prefetch models"
if [[ "$BACKEND" == "cpu" ]]; then
    # the worker refuses a backend nobody named (jax's own variable)
    echo "  JAX_PLATFORMS=cpu python -m chiaswarm_tpu.cli worker   # dev worker on the CPU"
else
    echo "  python -m chiaswarm_tpu.cli worker   # join the swarm"
fi
