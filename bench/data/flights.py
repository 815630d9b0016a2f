"""Seeded stand-in for US DOT BTS 2015 "Flight Delays and Cancellations".

One row per flight with the published file's 31 columns, under their names
in lower case. The carrier and month of a flight follow the published
per-carrier and per-month counts; the date gives the day of week from the
2015 calendar. Airports have fixed positions, so a route's distance is the
same on every flight, and the times follow from one another as in the file:
wheels off is departure plus taxi-out, elapsed time is taxi-out plus air
time plus taxi-in, arrival delay is departure delay plus the elapsed time
over the scheduled time, and the clock columns are ``hhmm``. The NULLs are
the file's pattern, at its published counts as shares: cancelled flights
(most without a departure, all without an arrival), diverted flights
(without an arrival delay, some without wheels-on), the cancellation reason
on cancelled flights only, and the five delay causes only where the arrival
delay is 15 minutes or more, splitting it. What the file does not publish
(the delay and taxi distributions, the airport shares and positions, the
tail numbers) is assumed; the configuration lists it under ``assumed``.
Imports nothing of the program, so a change there cannot move the
benchmark's data.
"""
from __future__ import annotations

import numpy as np

ROWS = 5_819_079
# Flights per carrier in the published file (they sum to ROWS).
AIRLINES = {"WN": 1_261_855, "DL": 875_881, "AA": 725_984, "OO": 588_353,
            "EV": 571_977, "UA": 515_723, "MQ": 294_632, "B6": 267_048,
            "US": 198_715, "AS": 172_521, "NK": 117_379, "F9": 90_836,
            "HA": 76_272, "VX": 61_903}
# Flights per month in the published file (they sum to ROWS).
MONTHS = (469_968, 429_191, 504_312, 485_151, 496_993, 503_897, 520_718,
          510_536, 464_946, 486_165, 467_972, 479_230)
DAYS = (31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31)
CANCELLED = 89_884
REASONS = {"A": 25_262, "B": 48_851, "C": 15_749, "D": 22}
NO_DEPARTURE = 86_153        # cancelled without departure time and delay
NO_TAXI_OUT = 89_047         # cancelled without taxi-out and wheels-off
DIVERTED = 15_187
DIVERTED_NO_WHEELS_ON = 92_513 - CANCELLED
NO_TAIL = 14_721             # all among the cancelled
AIRPORTS = 322
TAILS = 4_897
FLIGHT_NUMBERS = 9_855
# Delay causes, in the file's order, and their mean minutes where present.
CAUSES = {"air_system_delay": 13.48, "security_delay": 0.076,
          "airline_delay": 18.97, "late_aircraft_delay": 23.47,
          "weather_delay": 2.92}

COLUMNS = ("year", "month", "day", "day_of_week", "airline", "flight_number",
           "tail_number", "origin_airport", "destination_airport",
           "scheduled_departure", "departure_time", "departure_delay",
           "taxi_out", "wheels_off", "scheduled_time", "elapsed_time",
           "air_time", "distance", "wheels_on", "taxi_in",
           "scheduled_arrival", "arrival_time", "arrival_delay", "diverted",
           "cancelled", "cancellation_reason", *CAUSES)


def shares(counts) -> np.ndarray:
    c = np.asarray(counts, np.float64)
    return c / c.sum()


def hhmm(minutes: np.ndarray) -> np.ndarray:
    """Minutes after midnight (any integer) as the file's clock ``hhmm``."""
    m = np.mod(minutes, 1440)
    return (m // 60) * 100 + m % 60


def codes(k: int) -> np.ndarray:
    """``k`` three-letter codes, AAA, AAB, ..."""
    letters = np.array(list("ABCDEFGHIJKLMNOPQRSTUVWXYZ"))
    i = np.arange(k)
    return np.char.add(np.char.add(letters[i // 676], letters[i // 26 % 26]),
                       letters[i % 26])


def generate(n: int, seed: int) -> dict:
    """``n`` rows as a column dict, fixed by ``seed``: string (or object,
    None for NULL) arrays for the categorical columns, float64 (NaN for
    NULL) for the others."""
    rng = np.random.default_rng(seed)
    f = np.float64

    month = rng.choice(12, n, p=shares(MONTHS))
    day = np.floor(rng.random(n) * np.asarray(DAYS)[month]).astype(np.int64)
    day_of_year = np.cumsum((0,) + DAYS[:-1])[month] + day
    day_of_week = (day_of_year + 3) % 7 + 1      # 2015-01-01 was a Thursday

    names = np.array(list(AIRLINES))
    carrier = rng.choice(len(names), n, p=shares(list(AIRLINES.values())))
    per = np.maximum(1, np.round(TAILS * shares(list(AIRLINES.values())))
                     ).astype(np.int64)
    first = np.concatenate([[0], np.cumsum(per)[:-1]])
    tail = first[carrier] + np.floor(rng.random(n) * per[carrier]
                                     ).astype(np.int64)
    tails = np.array([f"N{i:04d}{names[c]}" for c, m in enumerate(per)
                      for i in range(m)], dtype=object)

    # Airports: Zipf-Mandelbrot shares and fixed positions in a box of
    # 2,100 by 950 miles.
    ports = codes(AIRPORTS)
    p_port = 1.0 / (np.arange(1, AIRPORTS + 1) + 15.0) ** 2
    p_port /= p_port.sum()
    xy = rng.uniform((0.0, 0.0), (2100.0, 950.0), size=(AIRPORTS, 2))
    origin = rng.choice(AIRPORTS, n, p=p_port)
    dest = rng.choice(AIRPORTS, n, p=p_port)
    dest = np.where(dest == origin, (dest + 1) % AIRPORTS, dest)
    distance = np.maximum(21.0, np.round(np.hypot(*(xy[origin] - xy[dest]).T)))

    sched_dep = np.where(rng.random(n) < 0.03,
                         rng.integers(0, 300, n), rng.integers(300, 1440, n))
    dep_delay = np.round(rng.exponential(11.0, n) - 5.0)
    late = rng.random(n) < 0.08
    dep_delay[late] += np.round(rng.exponential(60.0, int(late.sum())))
    dep_delay = np.minimum(dep_delay, 1988.0)
    taxi_out = 1.0 + np.round(np.abs(rng.normal(15.0, 9.0, n)))
    air_time = np.maximum(7.0, np.round(distance / 8.0 + 8.0
                                        + rng.normal(0.0, 7.0, n)))
    taxi_in = 1.0 + np.round(np.abs(rng.normal(6.0, 5.0, n)))
    elapsed = taxi_out + air_time + taxi_in
    sched_time = np.maximum(18.0, np.round(distance / 8.0 + 36.0
                                           + rng.normal(0.0, 6.0, n)))
    arr_delay = dep_delay + elapsed - sched_time
    arrival = sched_dep + sched_time + arr_delay

    # Cancelled and diverted flights, and which of their columns are NULL.
    state = rng.choice(3, n, p=shares([ROWS - CANCELLED - DIVERTED,
                                       CANCELLED, DIVERTED]))
    cancelled, diverted = state == 1, state == 2
    u = rng.random(n)
    no_dep = cancelled & (u < NO_DEPARTURE / CANCELLED)
    no_taxi = cancelled & (u < NO_TAXI_OUT / CANCELLED)
    no_tail = cancelled & (rng.random(n) < NO_TAIL / CANCELLED)
    no_wheels_on = cancelled | (diverted & (rng.random(n) < (
        DIVERTED_NO_WHEELS_ON / DIVERTED)))
    no_arrival = cancelled | diverted
    reason = np.full(n, None, dtype=object)
    reason[cancelled] = np.array(list(REASONS), dtype=object)[rng.choice(
        len(REASONS), int(cancelled.sum()), p=shares(list(REASONS.values())))]

    def null(x, where):
        x = np.asarray(x, f).copy()
        x[where] = np.nan
        return x

    out = {
        "year": np.full(n, 2015.0),
        "month": (month + 1).astype(f),
        "day": (day + 1).astype(f),
        "day_of_week": day_of_week.astype(f),
        "airline": names[carrier],
        "flight_number": rng.integers(1, FLIGHT_NUMBERS + 1, n).astype(f),
        "tail_number": np.where(no_tail, None, tails[tail]),
        "origin_airport": ports[origin],
        "destination_airport": ports[dest],
        "scheduled_departure": hhmm(sched_dep).astype(f),
        "departure_time": null(hhmm(sched_dep + dep_delay), no_dep),
        "departure_delay": null(dep_delay, no_dep),
        "taxi_out": null(taxi_out, no_taxi),
        "wheels_off": null(hhmm(sched_dep + dep_delay + taxi_out), no_taxi),
        "scheduled_time": sched_time,
        "elapsed_time": null(elapsed, no_arrival),
        "air_time": null(air_time, no_arrival),
        "distance": distance,
        "wheels_on": null(hhmm(arrival - taxi_in), no_wheels_on),
        "taxi_in": null(taxi_in, no_wheels_on),
        "scheduled_arrival": hhmm(sched_dep + sched_time).astype(f),
        "arrival_time": null(hhmm(arrival), no_wheels_on),
        "arrival_delay": null(arr_delay, no_arrival),
        "diverted": diverted.astype(f),
        "cancelled": cancelled.astype(f),
        "cancellation_reason": reason,
    }
    out.update(delay_causes(out["arrival_delay"], rng))
    return out


def delay_causes(arr_delay: np.ndarray, rng) -> dict:
    """The five delay causes: NULL unless the arrival delay is 15 minutes
    or more, and then whole minutes that sum to it, each cause's share
    drawn from a Dirichlet whose mean is its published mean share."""
    with np.errstate(invalid="ignore"):
        late = arr_delay >= 15
    total = arr_delay[late]
    mean = shares(list(CAUSES.values()))
    g = rng.gamma(np.maximum(0.6 * mean, 1e-3), 1.0, (len(total), len(mean)))
    g[g.sum(axis=1) == 0, 3] = 1.0           # all of it late aircraft
    parts = np.floor(total[:, None] * g / g.sum(axis=1, keepdims=True))
    top = np.argmax(g, axis=1)
    parts[np.arange(len(total)), top] += total - parts.sum(axis=1)
    out = {}
    for j, name in enumerate(CAUSES):
        col = np.full(len(arr_delay), np.nan)
        col[late] = parts[:, j]
        out[name] = col
    return out
