"""Scalar function library, SQL-level, vs Python-computed expectations.

The reference's analogue coverage: operator/scalar Test* classes
(presto-main/src/test/.../operator/scalar/, e.g. TestMathFunctions,
TestStringFunctions, TestDateTimeFunctions)."""

import datetime
import math

import pytest

from presto_tpu.localrunner import LocalQueryRunner


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner.tpch(scale=0.001)


def one(runner, sql):
    rows = runner.execute(sql).rows
    assert len(rows) == 1
    return rows[0]


class TestMath:
    def test_trig(self, runner):
        row = one(runner, "select sin(1.0), cos(1.0), tan(1.0), "
                          "asin(0.5), acos(0.5), atan(1.0), atan2(1.0, 2.0)")
        want = (math.sin(1), math.cos(1), math.tan(1), math.asin(.5),
                math.acos(.5), math.atan(1), math.atan2(1, 2))
        for got, exp in zip(row, want):
            assert math.isclose(got, exp)

    def test_hyperbolic_logs(self, runner):
        row = one(runner, "select sinh(1.0), cosh(1.0), tanh(1.0), "
                          "log2(8.0), log10(1000.0), ln(e()), exp(1.0)")
        want = (math.sinh(1), math.cosh(1), math.tanh(1), 3.0, 3.0, 1.0,
                math.e)
        for got, exp in zip(row, want):
            assert math.isclose(got, exp)

    def test_rounding_family(self, runner):
        row = one(runner, "select truncate(2.9), truncate(-2.9), "
                          "round(2.5), round(-2.5), round(2.345, 2), "
                          "ceil(2.1), floor(-2.1), cbrt(8.0)")
        assert row[:7] == (2.0, -2.0, 3.0, -3.0, 2.35, 3.0, -3.0)
        assert math.isclose(row[7], 2.0)

    def test_misc(self, runner):
        row = one(runner, "select abs(-7), sign(-3.5), mod(7, 3), "
                          "mod(-7, 3), power(2.0, 10.0), sqrt(2.0)")
        assert row[:4] == (7, -1.0, 1, -1)
        assert row[4] == 1024.0
        assert math.isclose(row[5], math.sqrt(2))

    def test_greatest_least_mixed(self, runner):
        row = one(runner, "select greatest(1, 2.5, 2), least(1, 2.5, 0), "
                          "greatest(3, 1), least(-1, -5)")
        assert row == (2.5, 0.0, 3, -5)

    def test_bitwise(self, runner):
        row = one(runner, "select bitwise_and(12, 10), bitwise_or(12, 10), "
                          "bitwise_xor(12, 10), bitwise_not(5)")
        assert row == (8, 14, 6, -6)

    def test_float_predicates(self, runner):
        row = one(runner, "select is_nan(nan()), is_finite(1.0), "
                          "is_infinite(infinity()), is_nan(1.0)")
        assert row == (True, True, True, False)


class TestString:
    def test_pad_split(self, runner):
        row = one(runner, "select lpad('ab', 5, 'xy'), rpad('ab', 5, 'xy'),"
                          " lpad('abcdef', 3, 'x'), "
                          "split_part('a:b:c', ':', 2)")
        assert row == ("xyxab", "abxyx", "abc", "b")

    def test_split_part_null(self, runner):
        row = one(runner, "select split_part('a:b', ':', 9) is null")
        assert row == (True,)

    def test_chr_codepoint(self, runner):
        row = one(runner, "select chr(9731), codepoint('A')")
        assert row == ("☃", 65)

    def test_translate_distance(self, runner):
        row = one(runner,
                  "select translate('abcd', 'abc', '12'), "
                  "levenshtein_distance('kitten', 'sitting'), "
                  "hamming_distance('karolin', 'kathrin')")
        assert row == ("12d", 3, 3)

    def test_regex(self, runner):
        row = one(runner,
                  "select regexp_like('plane', 'an'), "
                  "regexp_extract('1a 2b 3c', '(\\d+)([a-z])', 2), "
                  "regexp_replace('1a 2b', '\\d', '#'), "
                  "regexp_extract('xyz', '\\d+') is null")
        assert row == (True, "a", "#a #b", True)

    def test_classic_string_fns_on_column(self, runner):
        rows = runner.execute(
            "select upper(n_name), length(n_name), reverse(n_name), "
            "strpos(n_name, 'A'), ends_with(n_name, 'A') "
            "from nation where n_name = 'ALGERIA'").rows
        assert rows == [("ALGERIA", 7, "AIREGLA", 1, True)]


class TestDatetime:
    def test_date_trunc(self, runner):
        row = one(runner, "select date_trunc('year', date '1995-07-17'), "
                          "date_trunc('quarter', date '1995-07-17'), "
                          "date_trunc('month', date '1995-07-17'), "
                          "date_trunc('week', date '1995-07-17')")
        d = datetime.date
        assert row == (d(1995, 1, 1), d(1995, 7, 1), d(1995, 7, 1),
                       d(1995, 7, 17))  # 1995-07-17 is a Monday

    def test_date_trunc_timestamp(self, runner):
        row = one(runner,
                  "select date_trunc('hour', "
                  "timestamp '1995-07-17 13:45:31'), "
                  "date_trunc('day', timestamp '1995-07-17 13:45:31')")
        dt = datetime.datetime
        assert row == (dt(1995, 7, 17, 13), dt(1995, 7, 17))

    def test_date_diff_add(self, runner):
        row = one(runner,
                  "select date_diff('day', date '1995-01-01', "
                  "date '1995-03-01'), "
                  "date_diff('week', date '1995-01-01', date '1995-01-20'),"
                  "date_diff('month', date '1995-01-31', "
                  "date '1995-03-01'), "
                  "date_add('day', 30, date '1995-01-15'), "
                  "date_add('year', -1, date '1996-02-29')")
        d = datetime.date
        assert row == (59, 2, 2, d(1995, 2, 14), d(1995, 2, 28))

    def test_extract_time_fields(self, runner):
        row = one(runner,
                  "select extract(hour from "
                  "timestamp '1995-07-17 13:45:31'), "
                  "extract(minute from timestamp '1995-07-17 13:45:31'), "
                  "extract(second from timestamp '1995-07-17 13:45:31'), "
                  "extract(year from date '1995-07-17'), "
                  "extract(quarter from date '1995-07-17'), "
                  "extract(day from date '1995-07-17')")
        assert row == (13, 45, 31, 1995, 3, 17)

    def test_unixtime(self, runner):
        row = one(runner,
                  "select to_unixtime(timestamp '1970-01-02 00:00:00'), "
                  "from_unixtime(86400.0)")
        assert row[0] == 86400.0
        assert row[1] == datetime.datetime(1970, 1, 2)

    def test_last_day_of_month(self, runner):
        row = one(runner, "select last_day_of_month(date '1996-02-10'), "
                          "last_day_of_month(date '1995-12-05')")
        assert row == (datetime.date(1996, 2, 29),
                       datetime.date(1995, 12, 31))


class TestConditional:
    def test_if(self, runner):
        row = one(runner, "select if(true, 1, 2), if(false, 1, 2), "
                          "if(1 > 2, 'y'), if(2 > 1, 'y') ")
        assert row == (1, 2, None, "y")

    def test_nullif_coalesce(self, runner):
        row = one(runner, "select nullif(5, 5), nullif(5, 3), "
                          "coalesce(null, null, 7), coalesce(1, 2)")
        assert row == (None, 5, 7, 1)


class TestAggregateExtras:
    def test_bool_aggs(self, runner):
        rows = runner.execute(
            "select bool_and(n_regionkey < 5), bool_or(n_regionkey > 3), "
            "every(n_regionkey >= 0) from nation").rows
        assert rows == [(True, True, True)]

    def test_any_value(self, runner):
        rows = runner.execute(
            "select any_value(n_name) from nation "
            "where n_name = 'KENYA'").rows
        assert rows == [("KENYA",)]


DECIMAL_LITERALS_ARE_DOUBLE = pytest.mark.xfail(strict=True, reason=(
    "decimal literals are typed DOUBLE and folded in IEEE f64: 0.06 + 0.01 "
    "is one ulp under 0.07 on the CPU, while the chip's DOUBLE (an f32 "
    "pair) cannot see that ulp and answers as SQL's exact decimal "
    "arithmetic does (PR 25: TPC-H Q6 at SF1 is 123439380.03 on the chip, "
    "75348424.71 on the CPU engine).  ROADMAP S5: type decimal literals "
    "DECIMAL or fold them exactly; these then pass and the marker goes"))


class TestDecimalLiterals:
    """Pins the CPU-versus-chip divergence on a predicate at a decimal
    boundary until the literal typing is repaired."""

    @DECIMAL_LITERALS_ARE_DOUBLE
    def test_sum_of_decimal_literals_is_exact(self, runner):
        assert one(runner, "select 0.06 + 0.01 = 0.07") == (True,)

    @DECIMAL_LITERALS_ARE_DOUBLE
    def test_q6_bounds_include_the_boundary_discount(self, runner):
        """TPC-H Q6's BETWEEN 0.06 - 0.01 AND 0.06 + 0.01 means
        [0.05, 0.07]: rows at l_discount = 0.07 belong to the answer."""
        where = ("from lineitem where l_shipdate >= date '1994-01-01' "
                 "and l_shipdate < date '1995-01-01' and l_quantity < 24 "
                 "and l_discount between ")
        computed = one(runner, "select count(*), sum(l_extendedprice * "
                       "l_discount) " + where + "0.06 - 0.01 and 0.06 + 0.01")
        exact = one(runner, "select count(*), sum(l_extendedprice * "
                    "l_discount) " + where + "0.05 and 0.07")
        assert exact[0] > 0
        assert computed == exact
