"""Mean over the window's ticks of active slots over ``num_slots``, from
``Engine.stats()`` after each tick."""


def read(record, trace):
    if not record["occupancy"]:
        return None
    return 100.0 * sum(record["occupancy"]) / len(record["occupancy"])
