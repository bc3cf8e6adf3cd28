"""``accelerate-tpu test`` — run the bundled sanity script under launch
(reference commands/test.py:22-58)."""

from __future__ import annotations

import os
import subprocess
import sys


def test_command(args, extra) -> int:
    script = os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "test_utils", "scripts", "test_script.py"
    )
    env = dict(os.environ)
    if args.cpu or env.get("JAX_PLATFORMS") == "cpu":
        # virtual 8-device mesh so the sharded paths actually exercise
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    print(f"Running {script}")
    result = subprocess.call([sys.executable, script], env=env)
    if result == 0:
        print("Test is a success! You are ready for your distributed training!")
    return result


def add_parser(subparsers) -> None:
    p = subparsers.add_parser("test", help="run the bundled end-to-end sanity check")
    p.add_argument("--cpu", action="store_true", help="force an 8-device virtual CPU mesh")
    p.set_defaults(func=test_command)
