"""Operations and HBM bytes one ``kernels.market_clear.mbdf_demand`` call
needs, from its shapes: N services of K clients, M bid prices, ``iters``
bisection trips (the unpadded work; padding is not counted).

Per client lane, per price, per trip: 1 - t^C f, the floor, the square,
the division and the sum (6); per service and price, per trip, the
valuation weight, its product and the comparison (8).  The final demand
costs 6 per lane and price.  Bytes: alpha and t^C in, prices in, demands
out, in float32."""


def cost(n: int, k: int, m: int, iters: int) -> tuple[float, float]:
    flops = n * m * (iters * (6 * k + 8) + 6 * k)
    nbytes = 4 * (2 * n * k + 2 * n * m)
    return float(flops), float(nbytes)
