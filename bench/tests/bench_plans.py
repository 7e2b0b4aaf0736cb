"""Cell plans for the tests, including cells kept out of ``BENCHMARK.json``
whose drivers the tests still run at a small size."""
from bench import run

# mnist-ddpg32 is out of BENCHMARK.json: at N = 64 the program's Eq. 23a
# bill takes its sorted SIC, whose interference loses the weakest
# interferer to cancellation (PERF.md, Open questions).  Below N = 64 the
# program bills with its pairwise SIC, so the driver's checks hold here.
KEPT_OUT = {"mnist-ddpg32": ("hfl-mnist", "ddpg32", "ddpg_steps_per_s",
                             "steps/s")}


def plan_of(workload: str) -> dict:
    """``run.cell_plan``, or the plan of a kept-out cell from its files."""
    if workload not in KEPT_OUT:
        return run.cell_plan(workload)
    config, traffic, rate, unit = KEPT_OUT[workload]
    return {"cell": {"name": workload, "config": config, "traffic": traffic,
                     "chips": 1},
            "config": run.load_json(f"bench/configs/{config}.json"),
            "traffic": run.load_json(f"bench/traffic/{traffic}.json"),
            "end_to_end": [{"name": rate, "unit": unit},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}
