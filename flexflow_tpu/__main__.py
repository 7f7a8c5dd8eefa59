"""CLI driver — `python -m flexflow_tpu script.py [flags]`.

Reference analog: the `flexflow_python` interpreter (python/main.cc +
flexflow_top.py) which started Legion and ran the user script as the
top-level task. TPU-native there is no runtime to boot: the driver parses
reference-style flags into the default FFConfig, exposes it via
`flexflow_tpu.get_driver_config()`, and execs the script.
"""

from __future__ import annotations

import runpy
import sys

from flexflow_tpu.config import FFConfig

def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    # --platform cpu [--cpu-devices N]: configure the backend BEFORE any
    # jax backend touch
    if "--platform" in argv:
        i = argv.index("--platform")
        platform = argv[i + 1]
        del argv[i:i + 2]
        import jax

        jax.config.update("jax_platforms", platform)
        if "--cpu-devices" in argv:
            i = argv.index("--cpu-devices")
            from flexflow_tpu.parallel.compat import ensure_cpu_devices

            ensure_cpu_devices(int(argv[i + 1]))
            del argv[i:i + 2]
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        print("usage: python -m flexflow_tpu [--platform cpu "
              "[--cpu-devices N]] SCRIPT [flags]\n"
              "flags: -b/--batch-size -e/--epochs --devices --mesh "
              "data=2,model=4 --budget --only-data-parallel "
              "--import-strategy F --export-strategy F --profiling ...")
        return 0
    script, rest = argv[0], argv[1:]
    # stash the parsed config ON THE PACKAGE (not this module — under
    # `python -m` this file runs as '__main__' and a scripts' import of
    # flexflow_tpu.__main__ would be a fresh second instance)
    import flexflow_tpu

    flexflow_tpu._driver_config = FFConfig.from_args(rest)
    from flexflow_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.argv = [script] + rest
    runpy.run_path(script, run_name="__main__")
    return 0


if __name__ == "__main__":
    sys.exit(main())
