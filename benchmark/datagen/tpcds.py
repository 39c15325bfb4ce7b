"""The benchmark's own generator of the TPC-DS store channel.

Every column of the ten store-channel tables of the TPC-DS v3.2 schema is
defined here; a column is generated only when it is asked for, from a random
stream of its own (seeded by the run's seed, the table and the column), so the
values of a column never depend on which other columns a cell needs.  Row
counts come from the configuration's file (the specification's row-count
table for its scale factor); everything this generator decides itself is
listed there under ``assumed``.

Integer columns are int64; a nullable foreign key carries ``NULL_SK`` (-1)
where it is null, and :func:`to_arrow` turns that into an arrow null.  Money
is float64 rounded to cents (the NDS suite's ``--floats`` setting).  Strings
come as ``(codes, dictionary)`` pairs (:class:`Coded`), which the reference
reads without ever building a string per row.
"""

from __future__ import annotations

import zlib

import numpy as np

NULL_SK = -1
#: d_date_sk is the Julian day number; date_dim starts at 1900-01-02
FIRST_DATE_SK = 2415022
EPOCH_1900_01_02 = np.datetime64("1900-01-02")
#: sales run over five years, as dsdgen's do
SALES_FIRST = np.datetime64("1998-01-02")
SALES_LAST = np.datetime64("2003-01-02")
#: relative weight of a sales day by the specification's three zones
ZONE_WEIGHT = {"low": 1.0, "medium": 1.5, "high": 2.5}

GENDERS = ("M", "F")
MARITAL = ("M", "S", "D", "W", "U")
EDUCATION = ("Primary", "Secondary", "College", "2 yr Degree", "4 yr Degree",
             "Advanced Degree", "Unknown")
CREDIT = ("Low Risk", "Good", "High Risk", "Unknown")
BUY_POTENTIAL = ("0-500", "501-1000", "1001-5000", "5001-10000", ">10000",
                 "Unknown")
CATEGORIES = ("Women", "Men", "Children", "Shoes", "Music", "Jewelry",
              "Home", "Sports", "Books", "Electronics")
CLASSES = tuple(f"class{n:02d}" for n in range(1, 17))
COLORS = ("almond", "antique", "aquamarine", "azure", "beige", "bisque",
          "black", "blanched", "blue", "blush", "brown", "burlywood")
UNITS = ("Unknown", "Each", "Dozen", "Case", "Pallet", "Gross", "Box", "Lb",
         "Oz", "Ton", "Tsp", "Cup", "Bunch", "Bundle", "Dram", "Carton")
SIZES = ("petite", "small", "medium", "large", "extra large", "economy",
         "N/A")
STATES = ("TN", "GA", "AL", "SC", "NC", "KY", "VA", "FL", "MS", "TX", "OH",
          "IN", "IL", "MO", "AR", "LA")
STREET_TYPES = ("Street", "Ave", "Blvd", "Ct.", "Dr.", "Lane", "Pkwy", "RD",
                "Way", "Circle", "Cir.", "Wy", "Road", "Boulevard", "Court",
                "Drive", "Parkway", "ST", "Ln", "Avenue")
CITIES = tuple(f"City{n:03d}" for n in range(1, 201))
COUNTIES = tuple(f"County {n:03d}" for n in range(1, 101))
DAY_NAMES = ("Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
             "Friday", "Saturday")
YN = ("N", "Y")
FIRST_NAMES = tuple(f"First{n:03d}" for n in range(1, 501))
LAST_NAMES = tuple(f"Last{n:04d}" for n in range(1, 2001))
COUNTRIES = tuple(f"COUNTRY {n:03d}" for n in range(1, 201))


class Coded:
    """A string column as int32 codes into a small dictionary."""

    def __init__(self, codes, dictionary):
        self.codes = np.asarray(codes, dtype=np.int32)
        self.dictionary = np.asarray(dictionary, dtype=object)

    def __len__(self):
        return len(self.codes)

    def values(self):
        """One python string per row (tests and small tables only)."""
        return self.dictionary[self.codes]

    def equals(self, literal: str):
        """Row mask of ``column = literal``."""
        hit = np.nonzero(self.dictionary == literal)[0]
        return np.isin(self.codes, hit)


def business_id(n):
    """dsdgen-style 16-letter business key of the numbers ``n``."""
    n = np.asarray(n, dtype=np.int64)
    letters = np.zeros((len(n), 16), dtype=np.uint8) + ord("A")
    rest = n.copy()
    for pos in range(8):
        letters[:, pos] += (rest % 16).astype(np.uint8)
        rest //= 16
    return letters.view("S16").ravel().astype(str)


def _unique_coded(strings):
    dictionary, codes = np.unique(np.asarray(strings, dtype=object)
                                  .astype(str), return_inverse=True)
    return Coded(codes, dictionary)


class StoreChannel:
    """Tables of one seed at one scale.  ``rows`` maps table to row count."""

    TABLES = ("store_sales", "store_returns", "date_dim", "item", "store",
              "customer", "customer_address", "customer_demographics",
              "household_demographics", "promotion")

    def __init__(self, rows: dict, seed: int, null_fk_share: float = 0.045,
                 lines_per_ticket: int = 20):
        self.rows = {t: int(rows[t]) for t in self.TABLES}
        self.seed = int(seed)
        self.null_fk_share = float(null_fk_share)
        self.lines_per_ticket = int(lines_per_ticket)
        self._memo: dict = {}

    # -- plumbing ----------------------------------------------------------
    def rng(self, table: str, stream: str):
        return np.random.default_rng(
            [self.seed, zlib.crc32(table.encode()), zlib.crc32(stream.encode())])

    def column(self, table: str, name: str):
        """The column, generated on first use and kept."""
        key = (table, name)
        if key not in self._memo:
            maker = getattr(self, "_" + table)(name)
            self._memo[key] = maker
        return self._memo[key]

    #: the name the reference reads a column by
    col = column

    def n(self, table: str) -> int:
        return self.rows[table]

    def _sk(self, table):
        return np.arange(1, self.n(table) + 1, dtype=np.int64)

    def _fk(self, table, name, target, at=None, nullable=True):
        """Uniform foreign key into ``target``; null at the fact tables'
        rate when ``nullable``.  ``at`` gathers a per-ticket draw to rows."""
        r = self.rng(table, name)
        size = self.n(table) if at is None else int(at.max()) + 1
        v = r.integers(1, self.n(target) + 1, size, dtype=np.int64)
        if nullable:
            v[r.random(size) < self.null_fk_share] = NULL_SK
        return v if at is None else v[at]

    def _money(self, table, name, lo, hi, n=None):
        r = self.rng(table, name)
        return np.round(r.uniform(lo, hi, n or self.n(table)), 2)

    def _ints(self, table, name, lo, hi, n=None):
        return self.rng(table, name).integers(lo, hi + 1, n or self.n(table),
                                              dtype=np.int64)

    def _pick(self, table, name, words, n=None):
        codes = self.rng(table, name).integers(0, len(words),
                                               n or self.n(table))
        return Coded(codes, words)

    # -- date_dim ----------------------------------------------------------
    def _dates(self):
        return EPOCH_1900_01_02 + np.arange(self.n("date_dim"))

    def _date_dim(self, name):
        d = self._dates()
        sk = FIRST_DATE_SK + np.arange(self.n("date_dim"), dtype=np.int64)
        year = d.astype("datetime64[Y]").astype(np.int64) + 1970
        month = d.astype("datetime64[M]").astype(np.int64) % 12 + 1
        dom = (d - d.astype("datetime64[M]")).astype(np.int64) + 1
        dow = (d.astype("datetime64[D]").astype(np.int64) + 4) % 7  # 0=Sunday
        days = np.arange(self.n("date_dim"), dtype=np.int64)
        qoy = (month - 1) // 3 + 1
        first_dom = sk - (dom - 1)
        yes = lambda mask: Coded(mask.astype(np.int32), YN)
        simple = {
            "d_date_sk": lambda: sk,
            "d_date_id": lambda: _unique_coded(business_id(sk)),
            "d_date": lambda: d.astype("datetime64[D]"),
            "d_month_seq": lambda: (year - 1900) * 12 + month - 1,
            "d_week_seq": lambda: (days + 1) // 7 + 1,
            "d_quarter_seq": lambda: (year - 1900) * 4 + qoy,
            "d_year": lambda: year, "d_dow": lambda: dow,
            "d_moy": lambda: month, "d_dom": lambda: dom,
            "d_qoy": lambda: qoy, "d_fy_year": lambda: year,
            "d_fy_quarter_seq": lambda: (year - 1900) * 4 + qoy,
            "d_fy_week_seq": lambda: (days + 1) // 7 + 1,
            "d_day_name": lambda: Coded(dow, DAY_NAMES),
            "d_quarter_name": lambda: _unique_coded(
                np.char.add(np.char.add(year.astype(str), "Q"),
                            qoy.astype(str))),
            "d_holiday": lambda: yes((dom == 25) & (month == 12)
                                     | (dom == 1) & (month == 1)
                                     | (dom == 4) & (month == 7)),
            "d_weekend": lambda: yes((dow == 0) | (dow == 6)),
            "d_following_holiday": lambda: yes((dom == 26) & (month == 12)
                                               | (dom == 2) & (month == 1)
                                               | (dom == 5) & (month == 7)),
            "d_first_dom": lambda: first_dom,
            "d_last_dom": lambda: first_dom + 27,
            "d_same_day_ly": lambda: sk - 365,
            "d_same_day_lq": lambda: sk - 91,
            "d_current_day": lambda: yes(sk == FIRST_DATE_SK + 37619),
            "d_current_week": lambda: yes(np.zeros(len(sk), bool)),
            "d_current_month": lambda: yes(np.zeros(len(sk), bool)),
            "d_current_quarter": lambda: yes(np.zeros(len(sk), bool)),
            "d_current_year": lambda: yes(year == 2003),
        }
        return simple[name]()

    def sales_days(self):
        """(date_sk, probability) of the days on which the store sells:
        1998-01-02 to 2003-01-02, weighted by the three sales zones."""
        first = int((SALES_FIRST - EPOCH_1900_01_02).astype(np.int64))
        last = int((SALES_LAST - EPOCH_1900_01_02).astype(np.int64))
        last = min(last, self.n("date_dim") - 1)
        d = EPOCH_1900_01_02 + np.arange(first, last + 1)
        month = d.astype("datetime64[M]").astype(np.int64) % 12 + 1
        w = np.where(month <= 7, ZONE_WEIGHT["low"],
                     np.where(month <= 10, ZONE_WEIGHT["medium"],
                              ZONE_WEIGHT["high"]))
        sk = FIRST_DATE_SK + np.arange(first, last + 1, dtype=np.int64)
        return sk, w / w.sum()

    # -- small dimensions ----------------------------------------------------
    def _item(self, name):
        n = self.n("item")
        sk = self._sk("item")
        brand_id = lambda: (self._ints("item", "i_category_id", 1, 10) * 1000000
                            + self._ints("item", "i_class_id", 1, 16) * 1000
                            + self._ints("item", "brand_no", 1, 10))
        simple = {
            "i_item_sk": lambda: sk,
            # a slowly changing dimension: two revisions share a business key
            "i_item_id": lambda: _unique_coded(business_id((sk - 1) // 2 + 1)),
            "i_rec_start_date": lambda: np.datetime64("1997-10-27")
            + ((sk - 1) % 2) * 1096,
            "i_rec_end_date": lambda: np.where(
                (sk - 1) % 2 == 0, np.datetime64("2000-10-26"),
                np.datetime64("NaT")).astype("datetime64[D]"),
            "i_item_desc": lambda: _unique_coded(
                np.char.add("description of item ", ((sk - 1) // 2).astype(str))),
            "i_current_price": lambda: self._money("item", name, 0.09, 99.99),
            "i_wholesale_cost": lambda: self._money("item", name, 0.02, 89.0),
            "i_brand_id": brand_id,
            "i_brand": lambda: _unique_coded(
                np.char.add("brand #", self.column("item", "i_brand_id")
                            .astype(str))),
            "i_class_id": lambda: self._ints("item", "i_class_id", 1, 16),
            "i_class": lambda: Coded(
                self._ints("item", "i_class_id", 1, 16) - 1, CLASSES),
            "i_category_id": lambda: self._ints("item", "i_category_id", 1, 10),
            "i_category": lambda: Coded(
                self._ints("item", "i_category_id", 1, 10) - 1, CATEGORIES),
            "i_manufact_id": lambda: self._ints("item", name, 1, 1000),
            "i_manufact": lambda: _unique_coded(
                np.char.add("manufact #", self.column("item", "i_manufact_id")
                            .astype(str))),
            "i_size": lambda: self._pick("item", name, SIZES),
            "i_formulation": lambda: _unique_coded(
                np.char.add("formulation ", self._ints(
                    "item", name, 1, 100000).astype(str))),
            "i_color": lambda: self._pick("item", name, COLORS),
            "i_units": lambda: self._pick("item", name, UNITS),
            "i_container": lambda: Coded(np.zeros(n, np.int32), ("Unknown",)),
            "i_manager_id": lambda: self._ints("item", name, 1, 100),
            "i_product_name": lambda: _unique_coded(
                np.char.add("product ", sk.astype(str))),
        }
        return simple[name]()

    def _address_columns(self, table, prefix, name, n):
        zips = lambda: _unique_coded(np.char.zfill(
            self._ints(table, prefix + "zip", 10000, 10000 + 399, n)
            .astype(str), 5))
        simple = {
            "street_number": lambda: _unique_coded(
                self._ints(table, name, 1, 1000, n).astype(str)),
            "street_name": lambda: _unique_coded(np.char.add(
                "Street ", self._ints(table, name, 1, 500, n).astype(str))),
            "street_type": lambda: self._pick(table, name, STREET_TYPES, n),
            "suite_number": lambda: _unique_coded(np.char.add(
                "Suite ", self._ints(table, name, 0, 99, n).astype(str))),
            "city": lambda: self._pick(table, name, CITIES, n),
            "county": lambda: self._pick(table, name, COUNTIES, n),
            "zip": zips,
            "country": lambda: Coded(np.zeros(n, np.int32),
                                     ("United States",)),
            "gmt_offset": lambda: -5.0 - self._ints(
                table, name, 0, 3, n).astype(np.float64),
        }
        return simple[name[len(prefix):]]()

    def _store(self, name):
        n = self.n("store")
        sk = self._sk("store")
        if name[2:] in ("street_number", "street_name", "street_type",
                        "suite_number", "city", "county", "zip", "country",
                        "gmt_offset"):
            return self._address_columns("store", "s_", name, n)
        simple = {
            "s_store_sk": lambda: sk,
            "s_store_id": lambda: _unique_coded(business_id((sk - 1) // 2 + 1)),
            "s_rec_start_date": lambda: np.datetime64("1997-03-13")
            + ((sk - 1) % 2) * 1096,
            "s_rec_end_date": lambda: np.where(
                (sk - 1) % 2 == 0, np.datetime64("2000-03-12"),
                np.datetime64("NaT")).astype("datetime64[D]"),
            "s_closed_date_sk": lambda: np.where(
                self.rng("store", name).random(n) < 0.7, NULL_SK,
                self._ints("store", name + ".v", 2450815, 2451179)),
            "s_store_name": lambda: self._pick(
                "store", name, ("ought", "able", "pri", "ese", "anti", "cally",
                                "ation", "eing", "bar")),
            "s_number_employees": lambda: self._ints("store", name, 200, 300),
            "s_floor_space": lambda: self._ints("store", name, 5000000,
                                                10000000),
            "s_hours": lambda: self._pick("store", name,
                                          ("8AM-4PM", "8AM-8AM", "8AM-12AM")),
            "s_manager": lambda: self._pick("store", name, LAST_NAMES[:50]),
            "s_market_id": lambda: self._ints("store", name, 1, 10),
            "s_geography_class": lambda: Coded(np.zeros(n, np.int32),
                                               ("Unknown",)),
            "s_market_desc": lambda: _unique_coded(np.char.add(
                "market description ", sk.astype(str))),
            "s_market_manager": lambda: self._pick("store", name,
                                                   LAST_NAMES[50:100]),
            "s_division_id": lambda: np.ones(n, np.int64),
            "s_division_name": lambda: Coded(np.zeros(n, np.int32),
                                             ("Unknown",)),
            "s_company_id": lambda: np.ones(n, np.int64),
            "s_company_name": lambda: Coded(np.zeros(n, np.int32),
                                            ("Unknown",)),
            # most stores of a small scale factor sit in one state, as
            # dsdgen's do (SF1: all twelve in TN)
            "s_state": lambda: Coded(np.where(
                (self.rng("store", name).random(n) < 0.6) | (sk == 1), 0,
                self._ints("store", name + ".v", 1, 5)), STATES),
            "s_tax_precentage": lambda: self._money("store", name, 0.0, 0.11),
        }
        return simple[name]()

    def _customer(self, name):
        n = self.n("customer")
        sk = self._sk("customer")
        simple = {
            "c_customer_sk": lambda: sk,
            "c_customer_id": lambda: _unique_coded(business_id(sk)),
            "c_current_cdemo_sk": lambda: self._fk(
                "customer", name, "customer_demographics"),
            "c_current_hdemo_sk": lambda: self._fk(
                "customer", name, "household_demographics"),
            "c_current_addr_sk": lambda: self._fk(
                "customer", name, "customer_address", nullable=False),
            "c_first_shipto_date_sk": lambda: self._ints(
                "customer", name, 2449028, 2452678),
            "c_first_sales_date_sk": lambda: self._ints(
                "customer", name, 2448998, 2452648),
            "c_salutation": lambda: self._pick(
                "customer", name, ("Mr.", "Mrs.", "Ms.", "Miss", "Sir", "Dr.")),
            "c_first_name": lambda: self._pick("customer", name, FIRST_NAMES),
            "c_last_name": lambda: self._pick("customer", name, LAST_NAMES),
            "c_preferred_cust_flag": lambda: self._pick("customer", name, YN),
            "c_birth_day": lambda: self._ints("customer", name, 1, 28),
            "c_birth_month": lambda: self._ints("customer", name, 1, 12),
            "c_birth_year": lambda: self._ints("customer", name, 1924, 1992),
            "c_birth_country": lambda: self._pick("customer", name, COUNTRIES),
            "c_login": lambda: Coded(np.zeros(n, np.int32), ("",)),
            "c_email_address": lambda: _unique_coded(np.char.add(
                np.char.add("customer", sk.astype(str)), "@example.org")),
            "c_last_review_date_sk": lambda: self._ints(
                "customer", name, 2452283, 2452648),
        }
        return simple[name]()

    def _customer_address(self, name):
        n = self.n("customer_address")
        sk = self._sk("customer_address")
        if name[3:] in ("street_number", "street_name", "street_type",
                        "suite_number", "city", "county", "zip", "country",
                        "gmt_offset"):
            return self._address_columns("customer_address", "ca_", name, n)
        simple = {
            "ca_address_sk": lambda: sk,
            "ca_address_id": lambda: _unique_coded(business_id(sk)),
            "ca_state": lambda: self._pick("customer_address", name, STATES),
            "ca_location_type": lambda: self._pick(
                "customer_address", name, ("apartment", "condo",
                                           "single family")),
        }
        return simple[name]()

    def _customer_demographics(self, name):
        # the specification's cross product, in its column order:
        # 2 x 5 x 7 x 20 x 4 x 7 x 7 x 7 = 1,920,800
        i = np.arange(self.n("customer_demographics"), dtype=np.int64)
        radix = (("cd_gender", 2), ("cd_marital_status", 5),
                 ("cd_education_status", 7), ("cd_purchase_estimate", 20),
                 ("cd_credit_rating", 4), ("cd_dep_count", 7),
                 ("cd_dep_employed_count", 7), ("cd_dep_college_count", 7))
        if name == "cd_demo_sk":
            return i + 1
        for col, base in radix:
            digit = i % base
            i = i // base
            if col == name:
                break
        words = {"cd_gender": GENDERS, "cd_marital_status": MARITAL,
                 "cd_education_status": EDUCATION, "cd_credit_rating": CREDIT}
        if name in words:
            return Coded(digit, words[name])
        if name == "cd_purchase_estimate":
            return (digit + 1) * 500
        return digit

    def _household_demographics(self, name):
        # 20 x 6 x 10 x 6 = 7,200
        i = np.arange(self.n("household_demographics"), dtype=np.int64)
        if name == "hd_demo_sk":
            return i + 1
        radix = (("hd_income_band_sk", 20), ("hd_buy_potential", 6),
                 ("hd_dep_count", 10), ("hd_vehicle_count", 6))
        for col, base in radix:
            digit = i % base
            i = i // base
            if col == name:
                break
        if name == "hd_income_band_sk":
            return digit + 1
        if name == "hd_buy_potential":
            return Coded(digit, BUY_POTENTIAL)
        return digit

    def _promotion(self, name):
        n = self.n("promotion")
        sk = self._sk("promotion")
        if name.startswith("p_channel_") and name != "p_channel_details":
            # dsdgen's channel flags are mostly 'N'
            codes = (self.rng("promotion", name).random(n) < 0.1)
            return Coded(codes.astype(np.int32), YN)
        simple = {
            "p_promo_sk": lambda: sk,
            "p_promo_id": lambda: _unique_coded(business_id(sk)),
            "p_start_date_sk": lambda: self._ints("promotion", name, 2450100,
                                                  2450915),
            "p_end_date_sk": lambda: self._ints("promotion", name, 2450916,
                                                2451000),
            "p_item_sk": lambda: self._fk("promotion", name, "item"),
            "p_cost": lambda: np.full(n, 1000.0),
            "p_response_target": lambda: np.ones(n, np.int64),
            "p_promo_name": lambda: self._pick(
                "promotion", name, ("ought", "able", "pri", "ese", "anti",
                                    "cally", "ation", "eing", "bar", "n st")),
            "p_channel_details": lambda: _unique_coded(np.char.add(
                "channel details ", sk.astype(str))),
            "p_purpose": lambda: Coded(np.zeros(n, np.int32), ("Unknown",)),
            "p_discount_active": lambda: self._pick("promotion", name, YN),
        }
        return simple[name]()

    # -- facts -------------------------------------------------------------
    def _ticket_of_line(self):
        """0-based ticket index of every store_sales line: tickets of 1 to
        ``lines_per_ticket`` lines, cut at the table's row count."""
        key = ("store_sales", "#ticket")
        if key not in self._memo:
            n = self.n("store_sales")
            mean = (1 + self.lines_per_ticket) / 2
            tickets = int(n / mean * 1.05) + 16
            sizes = self.rng("store_sales", "#ticket").integers(
                1, self.lines_per_ticket + 1, tickets)
            while sizes.sum() < n:
                sizes = np.concatenate([sizes, sizes])
            t = np.repeat(np.arange(len(sizes), dtype=np.int64), sizes)[:n]
            self._memo[key] = t
        return self._memo[key]

    def _store_sales(self, name):
        n = self.n("store_sales")
        T = "store_sales"
        ticket = self._ticket_of_line
        col = lambda c: self.column(T, c)

        def sold_date():
            sk, p = self.sales_days()
            t = ticket()
            r = self.rng(T, name)
            per_ticket = sk[r.choice(len(sk), int(t.max()) + 1, p=p)]
            per_ticket[r.random(len(per_ticket)) < self.null_fk_share] = NULL_SK
            return per_ticket[t]

        def items():
            # the lines of a ticket name different items: a random start
            # and a stride that is coprime to the item count
            t = ticket()
            ni = self.n("item")
            start = self.rng(T, name).integers(0, ni, int(t.max()) + 1)
            first = np.r_[0, np.nonzero(np.diff(t))[0] + 1]
            line_no = np.arange(n) - np.repeat(first, np.diff(np.r_[first, n]))
            stride = 7919 if ni % 7919 else 7907
            return (start[t] + line_no * stride) % ni + 1

        rnd2 = lambda x: np.round(x, 2)
        qty = lambda: col("ss_quantity").astype(np.float64)
        simple = {
            "ss_sold_date_sk": sold_date,
            "ss_sold_time_sk": lambda: np.where(
                self.rng(T, name).random(n) < self.null_fk_share, NULL_SK,
                self._ints(T, name + ".v", 28800, 75599,
                           int(ticket().max()) + 1)[ticket()]),
            "ss_item_sk": items,
            "ss_customer_sk": lambda: self._fk(T, name, "customer", ticket()),
            "ss_cdemo_sk": lambda: self._fk(T, name, "customer_demographics",
                                            ticket()),
            "ss_hdemo_sk": lambda: self._fk(T, name, "household_demographics",
                                            ticket()),
            "ss_addr_sk": lambda: self._fk(T, name, "customer_address",
                                           ticket()),
            "ss_store_sk": lambda: self._fk(T, name, "store", ticket()),
            "ss_promo_sk": lambda: self._fk(T, name, "promotion"),
            "ss_ticket_number": lambda: ticket() + 1,
            "ss_quantity": lambda: self._ints(T, name, 1, 100),
            "ss_wholesale_cost": lambda: self._money(T, name, 1.0, 100.0),
            "ss_list_price": lambda: rnd2(
                col("ss_wholesale_cost")
                * (1.0 + self.rng(T, name).random(n))),
            "ss_sales_price": lambda: rnd2(
                col("ss_list_price") * (1.0 - self.rng(T, name).random(n))),
            "ss_ext_discount_amt": lambda: rnd2(
                (col("ss_list_price") - col("ss_sales_price")) * qty()),
            "ss_ext_sales_price": lambda: rnd2(col("ss_sales_price") * qty()),
            "ss_ext_wholesale_cost": lambda: rnd2(
                col("ss_wholesale_cost") * qty()),
            "ss_ext_list_price": lambda: rnd2(col("ss_list_price") * qty()),
            "ss_ext_tax": lambda: rnd2(
                col("ss_ext_sales_price")
                * self.rng(T, name).integers(0, 10, n) / 100.0),
            # most lines carry no coupon
            "ss_coupon_amt": lambda: np.where(
                self.rng(T, name).random(n) < 0.8, 0.0, rnd2(
                    col("ss_ext_sales_price")
                    * self.rng(T, name + ".v").random(n))),
            "ss_net_paid": lambda: rnd2(
                col("ss_ext_sales_price") - col("ss_coupon_amt")),
            "ss_net_paid_inc_tax": lambda: rnd2(
                col("ss_net_paid") + col("ss_ext_tax")),
            "ss_net_profit": lambda: rnd2(
                col("ss_net_paid") - col("ss_ext_wholesale_cost")),
        }
        return simple[name]()

    def _returned_lines(self):
        key = ("store_returns", "#lines")
        if key not in self._memo:
            lines = self.rng("store_returns", "#lines").choice(
                self.n("store_sales"), self.n("store_returns"), replace=False)
            lines.sort()
            self._memo[key] = lines
        return self._memo[key]

    def _store_returns(self, name):
        n = self.n("store_returns")
        T = "store_returns"
        lines = self._returned_lines
        sale = lambda c: self.column("store_sales", c)[lines()]
        col = lambda c: self.column(T, c)
        rnd2 = lambda x: np.round(x, 2)

        def returned_date():
            sold = sale("ss_sold_date_sk")
            lag = self._ints(T, name, 1, 90)
            last = FIRST_DATE_SK + self.n("date_dim") - 1
            out = np.minimum(sold + lag, last)
            out[(sold == NULL_SK)
                | (self.rng(T, name + ".null").random(n)
                   < self.null_fk_share)] = NULL_SK
            return out

        def share():
            return self.rng(T, "share").random(n)

        simple = {
            "sr_returned_date_sk": returned_date,
            "sr_return_time_sk": lambda: self._ints(T, name, 28800, 75599),
            "sr_item_sk": lambda: sale("ss_item_sk"),
            "sr_customer_sk": lambda: sale("ss_customer_sk"),
            "sr_cdemo_sk": lambda: self._fk(T, name, "customer_demographics"),
            "sr_hdemo_sk": lambda: self._fk(T, name, "household_demographics"),
            "sr_addr_sk": lambda: self._fk(T, name, "customer_address"),
            "sr_store_sk": lambda: sale("ss_store_sk"),
            "sr_reason_sk": lambda: np.where(
                self.rng(T, name).random(n) < self.null_fk_share, NULL_SK,
                self._ints(T, name + ".v", 1, 35)),
            "sr_ticket_number": lambda: sale("ss_ticket_number"),
            "sr_return_quantity": lambda: np.maximum(1, (
                sale("ss_quantity") * share()).astype(np.int64)),
            "sr_return_amt": lambda: rnd2(
                sale("ss_sales_price") * col("sr_return_quantity")),
            "sr_return_tax": lambda: rnd2(col("sr_return_amt") * 0.05),
            "sr_return_amt_inc_tax": lambda: rnd2(
                col("sr_return_amt") + col("sr_return_tax")),
            "sr_fee": lambda: self._money(T, name, 0.5, 100.0),
            "sr_return_ship_cost": lambda: rnd2(
                sale("ss_list_price") * 0.1 * col("sr_return_quantity")),
            "sr_refunded_cash": lambda: rnd2(
                col("sr_return_amt") * self.rng(T, name).random(n)),
            "sr_reversed_charge": lambda: rnd2(
                (col("sr_return_amt") - col("sr_refunded_cash"))
                * self.rng(T, name).random(n)),
            "sr_store_credit": lambda: rnd2(
                col("sr_return_amt") - col("sr_refunded_cash")
                - col("sr_reversed_charge")),
            "sr_net_loss": lambda: rnd2(
                col("sr_fee") + col("sr_return_ship_cost")
                + col("sr_return_tax")),
        }
        return simple[name]()


def _names(text):
    return tuple(text.split())


#: every column of the store channel, in the specification's order
SCHEMA = {
    "store_sales": _names(
        "ss_sold_date_sk ss_sold_time_sk ss_item_sk ss_customer_sk ss_cdemo_sk "
        "ss_hdemo_sk ss_addr_sk ss_store_sk ss_promo_sk ss_ticket_number "
        "ss_quantity ss_wholesale_cost ss_list_price ss_sales_price "
        "ss_ext_discount_amt ss_ext_sales_price ss_ext_wholesale_cost "
        "ss_ext_list_price ss_ext_tax ss_coupon_amt ss_net_paid "
        "ss_net_paid_inc_tax ss_net_profit"),
    "store_returns": _names(
        "sr_returned_date_sk sr_return_time_sk sr_item_sk sr_customer_sk "
        "sr_cdemo_sk sr_hdemo_sk sr_addr_sk sr_store_sk sr_reason_sk "
        "sr_ticket_number sr_return_quantity sr_return_amt sr_return_tax "
        "sr_return_amt_inc_tax sr_fee sr_return_ship_cost sr_refunded_cash "
        "sr_reversed_charge sr_store_credit sr_net_loss"),
    "date_dim": _names(
        "d_date_sk d_date_id d_date d_month_seq d_week_seq d_quarter_seq "
        "d_year d_dow d_moy d_dom d_qoy d_fy_year d_fy_quarter_seq "
        "d_fy_week_seq d_day_name d_quarter_name d_holiday d_weekend "
        "d_following_holiday d_first_dom d_last_dom d_same_day_ly "
        "d_same_day_lq d_current_day d_current_week d_current_month "
        "d_current_quarter d_current_year"),
    "item": _names(
        "i_item_sk i_item_id i_rec_start_date i_rec_end_date i_item_desc "
        "i_current_price i_wholesale_cost i_brand_id i_brand i_class_id "
        "i_class i_category_id i_category i_manufact_id i_manufact i_size "
        "i_formulation i_color i_units i_container i_manager_id "
        "i_product_name"),
    "store": _names(
        "s_store_sk s_store_id s_rec_start_date s_rec_end_date "
        "s_closed_date_sk s_store_name s_number_employees s_floor_space "
        "s_hours s_manager s_market_id s_geography_class s_market_desc "
        "s_market_manager s_division_id s_division_name s_company_id "
        "s_company_name s_street_number s_street_name s_street_type "
        "s_suite_number s_city s_county s_state s_zip s_country s_gmt_offset "
        "s_tax_precentage"),
    "customer": _names(
        "c_customer_sk c_customer_id c_current_cdemo_sk c_current_hdemo_sk "
        "c_current_addr_sk c_first_shipto_date_sk c_first_sales_date_sk "
        "c_salutation c_first_name c_last_name c_preferred_cust_flag "
        "c_birth_day c_birth_month c_birth_year c_birth_country c_login "
        "c_email_address c_last_review_date_sk"),
    "customer_address": _names(
        "ca_address_sk ca_address_id ca_street_number ca_street_name "
        "ca_street_type ca_suite_number ca_city ca_county ca_state ca_zip "
        "ca_country ca_gmt_offset ca_location_type"),
    "customer_demographics": _names(
        "cd_demo_sk cd_gender cd_marital_status cd_education_status "
        "cd_purchase_estimate cd_credit_rating cd_dep_count "
        "cd_dep_employed_count cd_dep_college_count"),
    "household_demographics": _names(
        "hd_demo_sk hd_income_band_sk hd_buy_potential hd_dep_count "
        "hd_vehicle_count"),
    "promotion": _names(
        "p_promo_sk p_promo_id p_start_date_sk p_end_date_sk p_item_sk p_cost "
        "p_response_target p_promo_name p_channel_dmail p_channel_email "
        "p_channel_catalog p_channel_tv p_channel_radio p_channel_press "
        "p_channel_event p_channel_demo p_channel_details p_purpose "
        "p_discount_active"),
}

#: foreign key -> the table it points into (validity is tested from this)
FOREIGN_KEYS = {
    "ss_sold_date_sk": "date_dim", "ss_item_sk": "item",
    "ss_customer_sk": "customer", "ss_cdemo_sk": "customer_demographics",
    "ss_hdemo_sk": "household_demographics", "ss_addr_sk": "customer_address",
    "ss_store_sk": "store", "ss_promo_sk": "promotion",
    "sr_returned_date_sk": "date_dim", "sr_item_sk": "item",
    "sr_customer_sk": "customer", "sr_cdemo_sk": "customer_demographics",
    "sr_hdemo_sk": "household_demographics", "sr_addr_sk": "customer_address",
    "sr_store_sk": "store", "c_current_cdemo_sk": "customer_demographics",
    "c_current_hdemo_sk": "household_demographics",
    "c_current_addr_sk": "customer_address", "p_item_sk": "item",
}

TABLE_OF_COLUMN = {c: t for t, cols in SCHEMA.items() for c in cols}


#: the columns NDS types as LongType whatever the identifier width
LONG_COLUMNS = frozenset({"ss_ticket_number", "sr_ticket_number"})


def to_arrow(column, name: str = "", integer_type: str = "int64"):
    """One generated column as a pyarrow array (nulls where NULL_SK/NaT).
    ``integer_type`` is the width of identifiers and integers."""
    import pyarrow as pa
    if isinstance(column, Coded):
        return pa.DictionaryArray.from_arrays(
            pa.array(column.codes), pa.array(column.dictionary.astype(str))
        ).cast(pa.string())
    column = np.asarray(column)
    if column.dtype.kind == "M":
        return pa.array(column.astype("datetime64[D]"), type=pa.date32())
    if column.dtype.kind in "iu":
        mask = column == NULL_SK
        width = np.int64 if name in LONG_COLUMNS else np.dtype(integer_type)
        return pa.array(column.astype(width, copy=False),
                        mask=mask if mask.any() else None)
    return pa.array(column.astype(np.float64, copy=False))


def arrow_table(gen: StoreChannel, table: str, columns,
                integer_type: str = "int64"):
    import pyarrow as pa
    return pa.table({c: to_arrow(gen.column(table, c), c, integer_type)
                     for c in columns})


# -- what the harness asks of any generator module (datagen/__init__.py) ----

def make(rows: dict, seed: int, **args) -> StoreChannel:
    return StoreChannel(rows, seed, **args)


def columns_named(words) -> dict:
    """table -> the schema's columns among ``words``, in schema order."""
    by_table: dict = {}
    for c in set(words) & set(TABLE_OF_COLUMN):
        by_table.setdefault(TABLE_OF_COLUMN[c], []).append(c)
    return {t: sorted(cols, key=SCHEMA[t].index)
            for t, cols in by_table.items()}


def arrow_tables(gen: StoreChannel, wanted: dict, integer_type: str) -> dict:
    """The arrow table of each table's wanted columns and its key."""
    return {table: arrow_table(
                gen, table, sorted(set(cols) | {SCHEMA[table][0]},
                                   key=SCHEMA[table].index), integer_type)
            for table, cols in wanted.items()}


def column_width(name: str, column) -> float:
    """Bytes per row of a column as the configuration types it (NDS
    --floats): identifiers and integers 4, ticket numbers and doubles 8,
    dates 4, strings their mean UTF-8 length plus a 4-byte offset."""
    if isinstance(column, Coded):
        lengths = np.array([len(str(s).encode()) for s in column.dictionary])
        return float(lengths[column.codes].mean()) + 4.0 if len(column) else 4.0
    kind = np.asarray(column).dtype.kind
    if kind == "M":
        return 4.0
    if kind == "f" or name in LONG_COLUMNS:
        return 8.0
    return 4.0
