"""batch_occupancy: real requests per launched batch in the window, from
the service's own counters (delta of occupancy_sum over delta of batches)."""


def read(record, trace, peak):
    if not record.get("batches"):
        return None
    return record["images_served"] / record["batches"]
