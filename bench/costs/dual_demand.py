"""Operations and HBM bytes one ``kernels.dual_demand`` call needs, from
its shapes: N services of K clients at one price each, ``iters`` bisection
trips of the Eq. 14 price -> frequency solve (the unpadded work; padding
is not counted).

Per client lane, per trip: 1 - t^C f and its floor (3), the square and
the division (2), the sum (1); per service, per trip, the midpoint (2),
(1 + f) times the sum (2), the residual and its sign (2) and the bracket
update (2).  Set-up costs 5 per lane (the valid mask, t^C masked, the
load's sum, the slowest client); the final demand and slope cost 14 per
lane (1 - t^C f again, the two slope sums, the demand sum) and 31 per
service (the bracket top, the price's inverse, the opt-out test and the
closed-form slope).  Bytes: alpha and t^C in, the price in, demand and
slope out, in float32."""


def cost(n: int, k: int, iters: int) -> tuple[float, float]:
    flops = n * (iters * (6 * k + 8) + 19 * k + 31)
    nbytes = 4 * (2 * n * k + 3 * n)
    return float(flops), float(nbytes)
