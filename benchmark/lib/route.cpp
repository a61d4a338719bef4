// The plain traversal of a LightGBM tree (numeric splits) over rows of a
// row-major float32 table. Built at run time by lib/reference.py; the
// numpy traversal there says the same and is what a machine without a
// compiler falls back to.
#include <cmath>
#include <cstdint>

// The leaf one row lands in; a tree's node arrays, LightGBM's decision.
static inline int32_t leaf_of(const float* row, const int32_t* split_feature,
                              const double* threshold,
                              const int32_t* decision_type,
                              const int32_t* left_child,
                              const int32_t* right_child) {
  const int32_t* const child[2] = {right_child, left_child};
  int32_t node = 0;
  while (node >= 0) {
    const double x = row[split_feature[node]];
    const int32_t dt = decision_type[node];
    const int32_t missing = (dt >> 2) & 3;  // 0 none, 1 zero, 2 nan
    const bool default_left = (dt & 2) != 0;
    bool left;
    if (std::isnan(x)) {  // NaN counts as 0 where the node's kind is not nan
      left = missing == 0 ? 0.0 <= threshold[node] : default_left;
    } else if (missing == 1 && std::fabs(x) <= 1e-35) {
      left = default_left;
    } else {
      left = x <= threshold[node];
    }
    node = child[left][node];  // no branch on the data
  }
  return ~node;
}

extern "C" void route_rows(const float* X, int64_t n, int32_t n_features,
                           const int32_t* split_feature,
                           const double* threshold,
                           const int32_t* decision_type,
                           const int32_t* left_child,
                           const int32_t* right_child, int32_t* leaf_out) {
  for (int64_t i = 0; i < n; ++i)
    leaf_out[i] = leaf_of(X + i * n_features, split_feature, threshold,
                          decision_type, left_child, right_child);
}
