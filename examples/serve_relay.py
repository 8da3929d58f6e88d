"""End-to-end driver (the paper's kind: serving): boots a live RelayGR
service — sequence-aware trigger, affinity router, HBM window, DRAM
expander, optional cold store, all orchestrated by the shared
event-driven RelayRuntime — over a real jitted HSTU model and replays a
batched synthetic request stream through the full
retrieval->preprocess->rank relay, printing the hit breakdown and the
trigger's admission ledger (plus the shipping / cold ledgers when those
tiers are enabled).

Run:  PYTHONPATH=src python examples/serve_relay.py [--requests 100]

With no arguments it serves the reduced --smoke model, sized for a
CPU; pass arguments without --smoke to serve hstu_gr at its published
widths.  The same launcher exposes every serving axis (see --help):

  --sim                         virtual-clock cluster sim at prod QPS
  --batched --max-batch 8       continuous micro-batching
  --page-tokens 64 --segments   paged window + beyond-prefix reuse
  --hosts 2 --prefill-hosts 1   multi-host + disaggregated prefill
  --dram-budget 4e9 --cold-budget 500e9   DRAM + SSD/remote cold tier

Also: PYTHONPATH=src python -m repro.launch.serve --sim   (cluster sim)
"""
import sys

from repro.launch.serve import main

if __name__ == "__main__":
    main(sys.argv[1:] or ["--smoke", "--requests", "100", "--qps", "150"])
