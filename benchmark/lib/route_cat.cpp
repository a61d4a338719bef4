// The plain traversal of a LightGBM tree with numeric AND categorical
// splits over rows of a row-major float32 table. Built at run time by
// lib/reference_cat.py; the numpy traversal there says the same and is
// what a machine without a compiler falls back to.
#include <cmath>
#include <cstdint>

// Numeric nodes as lib/route.cpp. A categorical node (decision-type bit
// 0) sends a row left iff its value is a non-negative integer whose bit
// is set in the node's bitset (the words cat_boundaries[k] ..
// cat_boundaries[k + 1] of cat_threshold, k the node's threshold); NaN,
// a negative value and an id past the bitset go right (LightGBM's
// Tree::CategoricalDecision).
static inline int32_t leaf_of(const float* row, const int32_t* split_feature,
                              const double* threshold,
                              const int32_t* decision_type,
                              const int32_t* left_child,
                              const int32_t* right_child,
                              const int32_t* cat_boundaries,
                              const uint32_t* cat_threshold) {
  const int32_t* const child[2] = {right_child, left_child};
  int32_t node = 0;
  while (node >= 0) {
    const double x = row[split_feature[node]];
    const int32_t dt = decision_type[node];
    bool left;
    if (dt & 1) {
      left = false;
      if (!std::isnan(x) && x >= 0.0 && x < 2147483648.0) {
        const int64_t id = static_cast<int64_t>(x);
        const int32_t k = static_cast<int32_t>(threshold[node]);
        const int64_t words = cat_boundaries[k + 1] - cat_boundaries[k];
        if ((id >> 5) < words)
          left = (cat_threshold[cat_boundaries[k] + (id >> 5)] >> (id & 31)) & 1u;
      }
    } else {
      const int32_t missing = (dt >> 2) & 3;  // 0 none, 1 zero, 2 nan
      const bool default_left = (dt & 2) != 0;
      if (std::isnan(x)) {  // NaN counts as 0 where the node's kind is not nan
        left = missing == 0 ? 0.0 <= threshold[node] : default_left;
      } else if (missing == 1 && std::fabs(x) <= 1e-35) {
        left = default_left;
      } else {
        left = x <= threshold[node];
      }
    }
    node = child[left][node];
  }
  return ~node;
}

extern "C" void route_rows_cat(const float* X, int64_t n, int32_t n_features,
                               const int32_t* split_feature,
                               const double* threshold,
                               const int32_t* decision_type,
                               const int32_t* left_child,
                               const int32_t* right_child,
                               const int32_t* cat_boundaries,
                               const uint32_t* cat_threshold,
                               int32_t* leaf_out) {
  for (int64_t i = 0; i < n; ++i)
    leaf_out[i] = leaf_of(X + i * n_features, split_feature, threshold,
                          decision_type, left_child, right_child,
                          cat_boundaries, cat_threshold);
}
