# swarm-tpu installer for Windows development hosts (parity with the
# reference's Install.ps1 venv bootstrap, /root/reference/Install.ps1:1-104).
#
# Windows machines have no TPU: this sets up the CPU jax backend, which
# runs the full hermetic test suite, the smoke harness, and the virtual
# multi-chip mesh (XLA_FLAGS=--xla_force_host_platform_device_count=8) for
# development. Production serving runs on TPU VMs via install.sh/Docker.

$ErrorActionPreference = "Stop"

if (-not [Environment]::Is64BitOperatingSystem) {
    Write-Error "swarm-tpu requires a 64-bit Windows installation"
    Exit 1
}

# Check for Python
try {
    $pythonVersion = (python --version).split(" ")[1]
}
catch {
    Write-Error "Unable to find python"
    Write-Output "Install Python 3.10+ from: https://docs.python.org/3/using/windows.html#installation-steps"
    Exit 1
}

$parts = $pythonVersion.split(".")
if ([int]$parts[0] -lt 3 -or ([int]$parts[0] -eq 3 -and [int]$parts[1] -lt 10)) {
    Write-Error "swarm-tpu requires Python 3.10+ (found $pythonVersion)"
    Exit 1
}

$venvDir = if ($env:VENV_DIR) { $env:VENV_DIR } else { ".venv" }

Write-Output "==> creating venv at $venvDir"
python -m venv $venvDir
& "$venvDir\Scripts\Activate.ps1"
python -m pip install --upgrade pip | Out-Null

Write-Output "==> installing swarm-tpu (cpu backend; deps from pyproject.toml)"
pip install -e ".[cpu,test]"

Write-Output ""
Write-Output "Install complete. Next steps:"
Write-Output "  .\$venvDir\Scripts\Activate.ps1"
Write-Output "  python -m chiaswarm_tpu.cli init      # configure hive + fetch models"
Write-Output "  `$env:JAX_PLATFORMS = 'cpu'             # the worker refuses a backend nobody named"
Write-Output "  python -m chiaswarm_tpu.node.smoke --all --random-weights"
Write-Output "  python -m pytest tests\ -q            # hermetic suite (CPU)"
