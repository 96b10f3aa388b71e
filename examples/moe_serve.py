"""Serving example: batched decode on the MoE arch (tile-fusion flagship).

  PYTHONPATH=src python examples/moe_serve.py
"""
from repro.launch import serve


def main():
    serve.main(["--arch", "granite-moe-3b-a800m", "--reduced",
                "--batch", "4", "--prompt-len", "16", "--gen", "24"])


if __name__ == "__main__":
    main()
