"""FLUDE core — the paper's contribution (C1–C5), as composable JAX modules."""
from repro.core.dependability import (BetaBelief, dependability, init_belief,
                                      sample_dependability, update_belief,
                                      variance)
from repro.core.selection import (SelectionResult, decay_epsilon,
                                  freq_threshold, priority,
                                  select_participants)
from repro.core.caching import (ClientCaches, adaptive_cache_interval,
                                clear_cache, expire_caches, gather_caches,
                                has_cache, init_caches, reset_caches,
                                resume_params, scatter_clear_cache,
                                scatter_write_cache, staleness, take_rows,
                                write_cache)
from repro.core.cache_store import (CohortCacheStream, HostCacheStore,
                                    TransferStats)
from repro.core.distribution import (DistributionPlan, DistributorState,
                                     init_distributor, plan_distribution,
                                     predicted_comm_cost)
from repro.core.aggregation import (PackLayout, aggregation_weights,
                                    fed_aggregate, fed_aggregate_delta,
                                    fed_aggregate_packed, pack, pack_layout,
                                    pack_stacked, unpack)
from repro.core.round import (FludePlan, FludeState, host_round_cut,
                              init_state, make_round_cut,
                              make_server_round_step, plan_round,
                              receive_quorum, update_after_round)
from repro.core.agg_rules import (AggRule, GeometricMedianRule, MeanRule,
                                  TrimmedMeanRule, TrustRule,
                                  available_agg_rules, get_agg_rule,
                                  make_agg_rule, register_agg_rule)
