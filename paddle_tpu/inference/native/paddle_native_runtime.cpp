// Native (python-free) serving runtime: executes jit.save's .pdnative
// artifact through the XLA CPU PJRT client.
//
// Reference analog: paddle/fluid/jit/layer.h:44 (jit::Layer — C++ execution
// of jit.save artifacts) and inference/api/analysis_predictor.cc — the
// reference serves saved programs from pure C++ with no Python linked. Here
// the saved program is an HloModuleProto (lowered by jax at save time) and
// the engine is xla::GetXlaPjrtCpuClient from libtensorflow_cc — this
// translation unit has NO Python.h and links NO libpython.
//
// Exposes the same PD_* C ABI subset as paddle_inference_c.cpp, so the same
// pure-C consumer program runs against either library; the CPython-embedding
// library remains the fallback for pass pipelines and TPU execution.
//
// Artifact format (jit/api.py _save_native_artifact):
//   PDNATIVE1
//   nparams N
//   param <name> <dtype> <ndim> <dims...>      x N
//   ninputs K
//   input <name> <dtype> <ndim> <dims...>      x K
//   noutputs M
//   output <name> <dtype> <ndim> <dims...>     x M
//   hlo <nbytes>
//   <raw HloModuleProto bytes><raw param buffers, in header order>

#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <fstream>
#include <map>
#include <memory>
#include <optional>
#include <sstream>
#include <string>
#include <vector>

#include "xla/hlo/builder/xla_computation.h"
#include "xla/literal.h"
#include "xla/pjrt/pjrt_client.h"
#include "xla/pjrt/plugin/xla_cpu/cpu_client_options.h"
#include "xla/pjrt/plugin/xla_cpu/xla_cpu_pjrt_client.h"
#include "xla/service/hlo.pb.h"
#include "xla/xla_data.pb.h"

namespace {

struct TensorMeta {
  std::string name;
  std::string dtype;
  std::vector<int64_t> dims;
  size_t nbytes() const {
    size_t n = item_size();
    for (auto d : dims) n *= static_cast<size_t>(d);
    return n;
  }
  // item_size and prim MUST cover the same dtype set: a dtype that passes
  // header validation (item_size != 0) but maps to PRIMITIVE_TYPE_INVALID
  // would fail later with an opaque upload error
  size_t item_size() const {
    if (dtype == "float32" || dtype == "int32" || dtype == "uint32") return 4;
    if (dtype == "float64" || dtype == "int64" || dtype == "uint64") return 8;
    if (dtype == "float16" || dtype == "bfloat16" || dtype == "int16" ||
        dtype == "uint16")
      return 2;
    if (dtype == "int8" || dtype == "uint8" || dtype == "bool") return 1;
    return 0;
  }
  xla::PrimitiveType prim() const {
    if (dtype == "float32") return xla::F32;
    if (dtype == "float64") return xla::F64;
    if (dtype == "float16") return xla::F16;
    if (dtype == "bfloat16") return xla::BF16;
    if (dtype == "int64") return xla::S64;
    if (dtype == "int32") return xla::S32;
    if (dtype == "int16") return xla::S16;
    if (dtype == "int8") return xla::S8;
    if (dtype == "uint64") return xla::U64;
    if (dtype == "uint32") return xla::U32;
    if (dtype == "uint16") return xla::U16;
    if (dtype == "uint8") return xla::U8;
    if (dtype == "bool") return xla::PRED;
    return xla::PRIMITIVE_TYPE_INVALID;
  }
};

xla::PjRtClient* client() {
  static std::unique_ptr<xla::PjRtClient> c = [] {
    xla::CpuClientOptions opts;
    auto r = xla::GetXlaPjrtCpuClient(opts);
    if (!r.ok()) {
      std::fprintf(stderr, "paddle_native: cpu client init failed: %s\n",
                   std::string(r.status().message()).c_str());
      return std::unique_ptr<xla::PjRtClient>();
    }
    return std::move(*r);
  }();
  return c.get();
}

// Header sanity bounds: a corrupt/truncated .pdnative must fail the load
// cleanly instead of driving nbytes() into overflow (and the subsequent
// std::string(nbytes, 0) into a bad_alloc or a huge read). Generous for any
// real model, fatal for garbage.
constexpr int kMaxNdim = 32;
constexpr int64_t kMaxDimExtent = int64_t{1} << 40;
constexpr size_t kMaxTensorBytes = size_t{1} << 40;  // 1 TiB per tensor
constexpr size_t kMaxTensorCount = size_t{1} << 20;
constexpr size_t kMaxHloBytes = size_t{1} << 32;     // 4 GiB program

struct Model {
  std::vector<TensorMeta> params, inputs, outputs;
  std::unique_ptr<xla::PjRtLoadedExecutable> exe;
  std::vector<std::unique_ptr<xla::PjRtBuffer>> param_bufs;  // uploaded once
  std::map<std::string, std::unique_ptr<xla::PjRtBuffer>> staged;
  std::vector<std::unique_ptr<xla::PjRtBuffer>> outs;

  bool load(const std::string& prefix);
  bool set_input(const char* name, const void* data,
                 const long long* shape, int ndim, const char* dtype);
  bool run();
};

std::unique_ptr<xla::PjRtBuffer> upload(const TensorMeta& m,
                                        const void* data) {
  auto* cl = client();
  if (!cl) return nullptr;
  auto ms = cl->addressable_devices()[0]->default_memory_space();
  if (!ms.ok()) return nullptr;
  // kImmutableOnlyDuringCall: the runtime copies synchronously inside this
  // call, so callers may free `data` the moment it returns (the param blob
  // and user input buffers both rely on this)
  auto buf = cl->BufferFromHostBuffer(
      data, m.prim(), absl::Span<const int64_t>(m.dims), std::nullopt,
      xla::PjRtClient::HostBufferSemantics::kImmutableOnlyDuringCall,
      nullptr, *ms, /*device_layout=*/nullptr);
  if (!buf.ok()) {
    std::fprintf(stderr, "paddle_native: upload failed: %s\n",
                 std::string(buf.status().message()).c_str());
    return nullptr;
  }
  return std::move(*buf);
}

bool Model::load(const std::string& prefix) {
  std::ifstream f(prefix + ".pdnative", std::ios::binary);
  if (!f) {
    std::fprintf(stderr, "paddle_native: cannot open %s.pdnative\n",
                 prefix.c_str());
    return false;
  }
  std::string magic;
  std::getline(f, magic);
  if (magic != "PDNATIVE1") return false;

  auto read_block = [&](const char* want, std::vector<TensorMeta>* out) {
    std::string kw;
    size_t n = 0;
    f >> kw >> n;
    // every extraction is checked before its value is trusted: a truncated
    // stream leaves garbage in the variables (and f in a fail state)
    if (!f || kw != std::string("n") + want + "s" || n > kMaxTensorCount)
      return false;
    for (size_t i = 0; i < n; ++i) {
      TensorMeta m;
      std::string kind;
      int ndim = 0;
      f >> kind >> m.name >> m.dtype >> ndim;
      if (!f || kind != want || m.item_size() == 0) return false;
      if (ndim < 0 || ndim > kMaxNdim) return false;
      size_t elems = 1;
      for (int d = 0; d < ndim; ++d) {
        int64_t v;
        f >> v;
        if (!f || v < 0 || v > kMaxDimExtent) return false;
        // overflow-guarded running product; total payload stays bounded
        if (v != 0 &&
            elems > kMaxTensorBytes / (static_cast<size_t>(v) * m.item_size()))
          return false;
        elems *= static_cast<size_t>(v);
        m.dims.push_back(v);
      }
      out->push_back(std::move(m));
    }
    return true;
  };
  if (!read_block("param", &params) || !read_block("input", &inputs) ||
      !read_block("output", &outputs))
    return false;
  std::string kw;
  size_t hlo_bytes = 0;
  f >> kw >> hlo_bytes;
  if (!f || kw != "hlo" || hlo_bytes == 0 || hlo_bytes > kMaxHloBytes)
    return false;
  f.get();  // the newline after the header
  std::string blob(hlo_bytes, '\0');
  f.read(&blob[0], static_cast<std::streamsize>(hlo_bytes));
  if (!f) return false;

  xla::HloModuleProto proto;
  if (!proto.ParseFromString(blob)) {
    std::fprintf(stderr, "paddle_native: HloModuleProto parse failed\n");
    return false;
  }
  auto* cl = client();
  if (!cl) return false;
  xla::XlaComputation comp(std::move(proto));
  xla::CompileOptions copts;
  auto exe_or = cl->CompileAndLoad(comp, copts);
  if (!exe_or.ok()) {
    std::fprintf(stderr, "paddle_native: compile failed: %s\n",
                 std::string(exe_or.status().message()).c_str());
    return false;
  }
  exe = std::move(*exe_or);

  // exact payload check: the raw param buffers are the tail of the file, so
  // their claimed sizes can never exceed the bytes actually remaining. This
  // is the real guard against huge-but-in-bounds dims — on overcommitting
  // kernels a 256 GiB std::string does not throw, it grinds the host into
  // the OOM killer while zero-filling pages.
  const std::streampos data_pos = f.tellg();
  f.seekg(0, std::ios::end);
  const std::streampos end_pos = f.tellg();
  f.seekg(data_pos);
  if (!f || end_pos < data_pos) return false;
  size_t remaining = static_cast<size_t>(end_pos - data_pos);
  for (const auto& m : params) {
    const size_t nb = m.nbytes();
    if (nb > remaining) {
      std::fprintf(stderr,
                   "paddle_native: param %s claims %zu bytes but only %zu "
                   "remain in the artifact\n",
                   m.name.c_str(), nb, remaining);
      return false;
    }
    remaining -= nb;
  }

  for (const auto& m : params) {
    std::string bytes(m.nbytes(), '\0');
    f.read(&bytes[0], static_cast<std::streamsize>(bytes.size()));
    if (!f) return false;
    auto b = upload(m, bytes.data());
    if (!b) return false;
    // the copy semantics above guarantee `bytes` is free to die here
    param_bufs.push_back(std::move(b));
  }
  return true;
}

bool Model::set_input(const char* name, const void* data,
                      const long long* shape, int ndim, const char* dtype) {
  for (const auto& m : inputs) {
    if (m.name == name) {
      if (m.dtype != dtype || ndim != static_cast<int>(m.dims.size()))
        return false;
      for (int i = 0; i < ndim; ++i)
        if (shape[i] != m.dims[i]) return false;
      auto b = upload(m, data);
      if (!b) return false;
      staged[m.name] = std::move(b);
      return true;
    }
  }
  return false;
}

bool Model::run() {
  if (!exe) return false;
  std::vector<xla::PjRtBuffer*> args;
  for (auto& b : param_bufs) args.push_back(b.get());
  for (const auto& m : inputs) {
    auto it = staged.find(m.name);
    if (it == staged.end()) return false;
    args.push_back(it->second.get());
  }
  xla::ExecuteOptions opts;
  // ExecuteSharded on the explicit device, the overload that fills no
  // future: the plain Execute path walks the compile-time device assignment
  // (not set by our default CompileOptions) and crashed inside the CPU client
  auto r = exe->ExecuteSharded(
      absl::Span<xla::PjRtBuffer* const>(args),
      client()->addressable_devices()[0], opts);
  if (!r.ok()) {
    std::fprintf(stderr, "paddle_native: execute failed: %s\n",
                 std::string(r.status().message()).c_str());
    return false;
  }
  outs = std::move(*r);
  return true;
}

}  // namespace

extern "C" {

#define PD_EXPORT __attribute__((visibility("default")))

struct PD_Config {
  std::string model;
};

struct PD_Predictor {
  Model model;
};

PD_EXPORT PD_Config* PD_ConfigCreate() { return new PD_Config(); }

PD_EXPORT void PD_ConfigSetModel(PD_Config* c, const char* model, const char* params) {
  (void)params;
  if (!c || !model) return;
  std::string m = model;
  // accept reference-style "<prefix>.pdmodel" paths like the capi library
  const std::string suffix = ".pdmodel";
  if (m.size() > suffix.size() &&
      m.compare(m.size() - suffix.size(), suffix.size(), suffix) == 0)
    m.resize(m.size() - suffix.size());
  c->model = m;
}

PD_EXPORT void PD_ConfigDestroy(PD_Config* c) { delete c; }

PD_EXPORT PD_Predictor* PD_PredictorCreate(PD_Config* c) {
  if (!c) return nullptr;
  auto* p = new PD_Predictor();
  // the C ABI must not leak exceptions: a corrupt header can declare dims
  // that pass the sanity bounds yet still exceed memory (std::bad_alloc from
  // the param staging string) — terminate()ing the host process would defeat
  // the fail-cleanly contract
  bool ok = false;
  try {
    ok = p->model.load(c->model);
  } catch (const std::exception& e) {
    std::fprintf(stderr, "paddle_native: load threw: %s\n", e.what());
  } catch (...) {
    std::fprintf(stderr, "paddle_native: load threw unknown exception\n");
  }
  if (!ok) {
    delete p;
    return nullptr;
  }
  return p;
}

PD_EXPORT void PD_PredictorDestroy(PD_Predictor* p) { delete p; }

PD_EXPORT int PD_PredictorSetInput(PD_Predictor* p, const char* name, const void* data,
                         const long long* shape, int ndim,
                         const char* dtype) {
  if (!p) return -1;
  try {
    return p->model.set_input(name, data, shape, ndim, dtype) ? 0 : -1;
  } catch (...) {
    std::fprintf(stderr, "paddle_native: set_input threw\n");
    return -1;
  }
}

// returns the number of outputs, or -1 (matching the CPython-bridge ABI)
PD_EXPORT int PD_PredictorRun(PD_Predictor* p) {
  if (!p) return -1;
  try {
    if (!p->model.run()) return -1;
    return static_cast<int>(p->model.outs.size());
  } catch (...) {
    std::fprintf(stderr, "paddle_native: run threw\n");
    return -1;
  }
}

PD_EXPORT int PD_PredictorGetOutputNum(PD_Predictor* p) {
  return p ? static_cast<int>(p->model.outputs.size()) : -1;
}

PD_EXPORT int PD_PredictorGetOutputShape(PD_Predictor* p, int idx, long long* shape_out,
                               int cap) {
  if (!p || idx < 0 || idx >= static_cast<int>(p->model.outputs.size()))
    return -1;
  const auto& dims = p->model.outputs[idx].dims;
  for (int i = 0; i < static_cast<int>(dims.size()) && i < cap; ++i)
    shape_out[i] = dims[i];
  return static_cast<int>(dims.size());
}

PD_EXPORT int PD_PredictorGetOutputDtype(PD_Predictor* p, int idx, char* buf, int cap) {
  if (!p || idx < 0 || idx >= static_cast<int>(p->model.outputs.size()))
    return -1;
  const auto& dt = p->model.outputs[idx].dtype;
  int n = static_cast<int>(dt.size());
  if (n >= cap) return -1;
  std::memcpy(buf, dt.c_str(), static_cast<size_t>(n) + 1);
  return n;
}

PD_EXPORT long long PD_PredictorGetOutputData(PD_Predictor* p, int idx, void* buf,
                                    long long cap) {
  if (!p || idx < 0 || idx >= static_cast<int>(p->model.outs.size()))
    return -1;
  auto& b = p->model.outs[idx];
  auto nbytes = p->model.outputs[idx].nbytes();
  if (static_cast<long long>(nbytes) > cap)
    return static_cast<long long>(nbytes);
  // Readback MUST go through TF's out-of-line PjRtBuffer::ToLiteralSync:
  // the header's inline Future<>::Await instantiates tsl::AsyncValue
  // accessors in THIS translation unit, whose type-ids do not match the
  // ones minted inside libtensorflow (observed as a fatal
  // "IsTypeIdCompatible" check). dlsym resolves the library's own
  // definition, so the await runs entirely on its side of the boundary.
  using ToLiteralFn =
      absl::StatusOr<std::shared_ptr<xla::Literal>> (*)(xla::PjRtBuffer*);
  static ToLiteralFn to_literal = reinterpret_cast<ToLiteralFn>(
      dlsym(RTLD_DEFAULT, "_ZN3xla10PjRtBuffer13ToLiteralSyncEv"));
  if (!to_literal) {
    std::fprintf(stderr, "paddle_native: ToLiteralSync symbol missing\n");
    return -1;
  }
  auto lit = to_literal(b.get());
  if (!lit.ok()) {
    std::fprintf(stderr, "paddle_native: readback failed: %s\n",
                 std::string(lit.status().message()).c_str());
    return -1;
  }
  const void* src = (*lit)->untyped_data({});
  size_t n = (*lit)->size_bytes({});
  if (n != nbytes) {
    std::fprintf(stderr, "paddle_native: size mismatch %zu != %zu\n", n,
                 nbytes);
    return -1;
  }
  std::memcpy(buf, src, n);
  return static_cast<long long>(nbytes);
}

}  // extern "C"
