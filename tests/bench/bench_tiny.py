"""A copy of the benchmark at a size a CPU test can run: each cell's
configuration cut to two layers of small widths (its query heads per key
head kept) and its mix to short contexts on four slots, all under one
temporary root. The Pallas kernels run in interpret mode."""
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the tiny configurations' limit on the widest logit gap. Readings at this
#: size (CPU, interpret-mode kernels, seeds 1-4 of each cell, 3 s windows):
#: the program 0.00-0.28, the float8 control 0.36-0.79. The tests use
#: seeds 1 and 2, which read at most 0.22.
TINY_LIMIT = 0.3


def shrink_config(conf: dict) -> dict:
    m = conf["model"]
    ratio = m["num_attention_heads"] // m["num_key_value_heads"]
    conf = json.loads(json.dumps(conf))
    conf["model"].update({
        "num_hidden_layers": 2, "hidden_size": 256,
        "num_attention_heads": 8, "num_key_value_heads": 8 // ratio,
        "head_dim": 32, "intermediate_size": 512, "vocab_size": 1000})
    conf["serving"].update({"prefill_chunk": 64,
                            "attention_backend": "interpret"})
    conf["serving"]["keys"]["group_size"] = 32
    conf["check"]["max_logit_gap"] = TINY_LIMIT
    return conf


def shrink_mix(mix: dict) -> dict:
    """A closed-loop mix cut to four clients on four slots."""
    return dict(mix, max_len=256, slots=4, clients=4,
                requests_per_client=40, check_requests=3,
                prompt={"dist": "uniform", "min": 100, "max": 200},
                output={"dist": "uniform", "min": 10, "max": 40})


#: an open-loop cell the benchmark does not hold yet (its rate needs a
#: knee found on the chip), so that the open-loop driver and the tail
#: metrics run here too
OPEN_CELL = "granite-8b.tiny-open"
OPEN_MIX = {"name": "tiny-open", "kind": "poisson", "why": "x", "slots": 4,
            "rate": 4.0, "max_len": 256, "check_requests": 4,
            "prompt": {"dist": "lognormal", "median": 60, "sigma": 1.0,
                       "min": 16, "max": 150},
            "output": {"dist": "lognormal", "median": 12, "sigma": 0.8,
                       "min": 4, "max": 40}}
OPEN_METRICS = [
    {"name": "ttft_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
     "source": "host_clock", "workloads": [OPEN_CELL]},
    {"name": "itl_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
     "source": "host_clock", "workloads": [OPEN_CELL]}]


def make_root(tmp: str) -> str:
    """The benchmark under ``tmp`` with every configuration and mix cut to
    the tiny size, under the same names, and :data:`OPEN_CELL` added."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    for c in man["configs"]:
        path = os.path.join(tmp, c["file"])
        with open(path) as f:
            conf = json.load(f)
        with open(path, "w") as f:
            json.dump(shrink_config(conf), f)
    for w in man["workloads"]:
        path = os.path.join(tmp, "bench", "traffic", w["traffic"] + ".json")
        with open(path) as f:
            mix = json.load(f)
        if mix.get("max_len", 0) > 256:
            with open(path, "w") as f:
                json.dump(shrink_mix(mix), f)
    with open(os.path.join(tmp, "bench", "traffic", "tiny-open.json"),
              "w") as f:
        json.dump(OPEN_MIX, f)
    man["workloads"].append({"name": OPEN_CELL, "config": "granite-8b.s9",
                             "traffic": "tiny-open", "chips": 1, "why": "x"})
    man["end_to_end"] += OPEN_METRICS
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return tmp
