"""Mean share of the exact top-10 that the served answers hold, over
the requests that the correctness comparison scores."""


def read(rec):
    if rec["geometry"]["k"] != 10:
        return None
    return rec["recall"]
