select d_year, i_category_id, i_category, sum(ss_ext_sales_price) s
from date_dim dt, store_sales, item
where dt.d_date_sk = store_sales.ss_sold_date_sk
  and store_sales.ss_item_sk = item.i_item_sk
  and item.i_manager_id = 1
  and dt.d_moy = [MONTH]
  and dt.d_year = [YEAR]
group by d_year, i_category_id, i_category
order by s desc, d_year, i_category_id, i_category
limit 100
