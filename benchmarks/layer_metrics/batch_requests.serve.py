"""Requests per dispatched batch over the window: ``requests`` over
``batches`` from ``/stats`` -> ``bank_engine``, snapshotted around it."""


def read(obs):
    engine = obs.get("engine")
    if not engine or not engine.get("batches"):
        return None
    return engine["requests"] / engine["batches"]
