SELECT SUM(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= {SHIPDATE_MIN}
  AND l_shipdate < {SHIPDATE_END}
  AND l_discount BETWEEN {DISCOUNT_LO} AND {DISCOUNT_HI}
  AND l_quantity < {QUANTITY}
