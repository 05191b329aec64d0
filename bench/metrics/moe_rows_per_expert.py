"""(row, expert) pairs the held experts computed per held expert, expert
layer and engine step of the window (ServeEngine.stats moe_held_rows over
the window): the batch each expert's weights are read for.  None where
the engine keeps no such counter."""


def read(rec):
    rows = rec.counters.get("moe_held_rows")
    n = sum(1 for s in rec.steps if not s.get("drain"))
    if rows is None or not n:
        return None
    cfg = rec.config
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return rows / (n * layers * cfg["n_routed_experts"])
