"""Lower + compile one (arch x shape) on the production meshes and print
its roofline terms — the per-pair version of the full dry-run sweep.

    PYTHONPATH=src python examples/multipod_dryrun.py qwen3-0.6b decode_32k
"""
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).parent.parent
arch = sys.argv[1] if len(sys.argv) > 1 else "qwen3-0.6b"
shape = sys.argv[2] if len(sys.argv) > 2 else "decode_32k"

for extra in ([], ["--multi-pod"]):
    subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", arch, "--shape", shape] + extra,
        env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        cwd=ROOT, check=True)
