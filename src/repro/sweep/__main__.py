"""``python -m repro.sweep`` — see repro/sweep_cli.py."""

from repro.sweep_cli import main

if __name__ == "__main__":
    main(compile_cache=True)
