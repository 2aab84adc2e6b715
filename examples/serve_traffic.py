"""Device-cloud serving under a traffic curve: DeviceFlow replays request
arrivals against a batched prefill+decode server (paper §I system-level
concern, LM edition).

Run:  PYTHONPATH=src python examples/serve_traffic.py
"""
import sys

from repro.launch.serve import main

sys.exit(main(["--arch", "llama3_2_3b", "--smoke", "--requests", "32",
               "--batch-size", "4", "--sigma", "1.0"]))
