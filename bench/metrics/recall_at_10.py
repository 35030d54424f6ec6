"""Mean share of the exact top-10 found among the first 10 served
answers (top-k's head where k < 10), over the requests that the
correctness comparison scores."""


def read(rec):
    return rec["recall"]
