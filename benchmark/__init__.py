"""The benchmark of lodestar-tpu: the node's BLS verification path on
the chip, one cell at a time (`python3 -m benchmark.run --help`).

Found by name from BENCHMARK.json: `configs/<config>.json`
(deployments), `traffic/<traffic>.json` (traffic mixes), `loops/<loop>.py`
(the generator and loop that a traffic file's `loop` names: it makes
the traffic from the seed, offers it to the node and chooses what is
compared), `metrics/<metric>.py` (one reader per metric).  The yardstick
lives here too: the plain reference (`reference/`), the comparison that
decides `correct` (`check.py`), the control (`control.py`) and the trace
reduction (`trace_reduce.py`).
"""
