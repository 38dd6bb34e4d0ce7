"""Engine: draft tokens accepted over draft tokens proposed in the window
(the scheduler's ``accepted`` / ``drafted`` counters, differenced). The
more a cycle accepts, the more tokens it hands out."""


def read(r):
    drafted = r.counters1["drafted"] - r.counters0["drafted"]
    accepted = r.counters1["accepted"] - r.counters0["accepted"]
    return accepted / drafted if drafted else None
