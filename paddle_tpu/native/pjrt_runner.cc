// PJRT C API runner: load a PJRT plugin (.so exporting GetPjrtApi),
// compile the bundle's exported StableHLO module, execute it — no
// Python, no JAX. This is the full-graph Python-free serving path
// (VERDICT r4 item 5): `merge_model` embeds the jax.export StableHLO of
// the forward in the bundle (io/merged_model.py export_forward_stablehlo)
// and any host with a local PJRT plugin (a real TPU host ships
// libtpu.so, which exports GetPjrtApi) serves it through this runner.
// The dense-subset interpreter (infer_engine.cc) remains the
// plugin-less fallback.
//
// Build: make pjrt  (header-only dependency: xla/pjrt/c/pjrt_c_api.h,
// vendored under third_party/; see Makefile).
//
// C ABI (ctypes-friendly; declared in capi.h):
//   ptpu_pjrt_create(plugin_so, mlir_bytes, len)  -> handle | NULL
//   ptpu_pjrt_device_count(h) / ptpu_pjrt_num_outputs(h)
//   ptpu_pjrt_execute_n(h, args[], nargs, results[], nresults)
//       n typed args -> n typed results (ptpu_pjrt_tensor signature
//       structs; the bundle's recorded input/output signature)
//   ptpu_pjrt_execute(h, in, rows, cols, out, cap, &elems)
//       legacy 1xf32-arg/first-result shim over execute_n
//   ptpu_pjrt_destroy(h) / ptpu_pjrt_last_error()

#include <dlfcn.h>

#include <cstdint>
#include <cstring>
#include <memory>
#include <string>
#include <vector>

#include "capi.h"
#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

thread_local std::string g_err;

#define CHECK_PJRT(api, expr)                                   \
  do {                                                          \
    PJRT_Error* _e = (expr);                                    \
    if (_e != nullptr) {                                        \
      PJRT_Error_Message_Args _m;                               \
      memset(&_m, 0, sizeof(_m));                               \
      _m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;     \
      _m.error = _e;                                            \
      (api)->PJRT_Error_Message(&_m);                           \
      g_err.assign(_m.message, _m.message_size);                \
      PJRT_Error_Destroy_Args _d;                               \
      memset(&_d, 0, sizeof(_d));                               \
      _d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;     \
      _d.error = _e;                                            \
      (api)->PJRT_Error_Destroy(&_d);                           \
      return nullptr;                                           \
    }                                                           \
  } while (0)

// Plugin create options parsed from "key=value;key=value" (all-digit
// values ride as kInt64, everything else as kString — the two types
// plugin option dicts use in practice).
struct Options {
  std::vector<std::string> keys, svals;
  std::vector<int64_t> ivals;
  std::vector<bool> is_int;
  std::vector<PJRT_NamedValue> named;

  explicit Options(const char* spec) {
    if (spec == nullptr) return;
    std::string s(spec);
    size_t pos = 0;
    while (pos < s.size()) {
      size_t semi = s.find(';', pos);
      if (semi == std::string::npos) semi = s.size();
      std::string kv = s.substr(pos, semi - pos);
      pos = semi + 1;
      size_t eq = kv.find('=');
      if (eq == std::string::npos || eq == 0) continue;
      keys.push_back(kv.substr(0, eq));
      std::string v = kv.substr(eq + 1);
      bool digits = !v.empty() &&
                    v.find_first_not_of("0123456789") == std::string::npos;
      is_int.push_back(digits);
      svals.push_back(v);
      ivals.push_back(digits ? strtoll(v.c_str(), nullptr, 10) : 0);
    }
    for (size_t i = 0; i < keys.size(); ++i) {
      PJRT_NamedValue nv;
      memset(&nv, 0, sizeof(nv));
      nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
      nv.name = keys[i].c_str();
      nv.name_size = keys[i].size();
      if (is_int[i]) {
        nv.type = PJRT_NamedValue_kInt64;
        nv.int64_value = ivals[i];
        nv.value_size = 1;
      } else {
        nv.type = PJRT_NamedValue_kString;
        nv.string_value = svals[i].c_str();
        nv.value_size = svals[i].size();
      }
      named.push_back(nv);
    }
  }
};

struct Runner {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_Device* device = nullptr;
  size_t num_devices = 0;
  // compiled programs over the ONE client: program 0 is the module
  // handed to create; ptpu_pjrt_add_program appends (the serving
  // daemon's decode init/step modules ride beside the forward)
  struct Prog {
    PJRT_LoadedExecutable* exec = nullptr;
    size_t num_results = 0;   // cached at compile
  };
  std::vector<Prog> progs;

  Prog* prog(int32_t i) {
    return (i >= 0 && i < int32_t(progs.size())) ? &progs[size_t(i)]
                                                 : nullptr;
  }

  ~Runner() {
    if (api != nullptr) {
      for (Prog& p : progs) {
        if (p.exec == nullptr) continue;
        PJRT_LoadedExecutable_Destroy_Args a;
        memset(&a, 0, sizeof(a));
        a.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
        a.executable = p.exec;
        api->PJRT_LoadedExecutable_Destroy(&a);
      }
      if (client != nullptr) {
        PJRT_Client_Destroy_Args a;
        memset(&a, 0, sizeof(a));
        a.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
        a.client = client;
        api->PJRT_Client_Destroy(&a);
      }
    }
    if (dl != nullptr) dlclose(dl);
  }
};

// CHECK_PJRT for int-returning functions: record g_err, return -1.
#define CHECK_PJRT_RC(api, expr)                                \
  do {                                                          \
    PJRT_Error* _e = (expr);                                    \
    if (_e != nullptr) {                                        \
      PJRT_Error_Message_Args _m;                               \
      memset(&_m, 0, sizeof(_m));                               \
      _m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;     \
      _m.error = _e;                                            \
      (api)->PJRT_Error_Message(&_m);                           \
      g_err.assign(_m.message, _m.message_size);                \
      PJRT_Error_Destroy_Args _d;                               \
      memset(&_d, 0, sizeof(_d));                               \
      _d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;     \
      _d.error = _e;                                            \
      (api)->PJRT_Error_Destroy(&_d);                           \
      return -1;                                                \
    }                                                           \
  } while (0)

// Minimal serialized xla.CompileOptionsProto:
//   executable_build_options (field 3, msg) {
//     num_replicas (field 4, varint) = 1
//     num_partitions (field 5, varint) = 1
//   }
const unsigned char kCompileOptions[] = {0x1A, 0x04, 0x20, 0x01, 0x28, 0x01};

// Compile one StableHLO module on the runner's client and append it to
// the program table; returns the program index or -1 (g_err set).
int compile_program(Runner* r, const char* code, size_t code_size) {
  const PJRT_Api* api = r->api;
  PJRT_Program prog;
  memset(&prog, 0, sizeof(prog));
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = const_cast<char*>(code);
  prog.code_size = code_size;
  prog.format = "mlir";
  prog.format_size = 4;
  PJRT_Client_Compile_Args a;
  memset(&a, 0, sizeof(a));
  a.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  a.client = r->client;
  a.program = &prog;
  a.compile_options = reinterpret_cast<const char*>(kCompileOptions);
  a.compile_options_size = sizeof(kCompileOptions);
  CHECK_PJRT_RC(api, api->PJRT_Client_Compile(&a));
  Runner::Prog p;
  p.exec = a.executable;
  // push BEFORE the post-compile queries: an error below then leaves a
  // registered program ~Runner destroys, instead of leaking the
  // compiled executable (device memory) on a flaky plugin — add_program
  // retries would pile those up
  r->progs.push_back(p);
  Runner::Prog& reg = r->progs.back();
  // cache the module's result count (execute validates against it)
  PJRT_LoadedExecutable_GetExecutable_Args g;
  memset(&g, 0, sizeof(g));
  g.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  g.loaded_executable = reg.exec;
  CHECK_PJRT_RC(api, api->PJRT_LoadedExecutable_GetExecutable(&g));
  PJRT_Executable_NumOutputs_Args n;
  memset(&n, 0, sizeof(n));
  n.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  n.executable = g.executable;
  PJRT_Error* nerr = api->PJRT_Executable_NumOutputs(&n);
  PJRT_Executable_Destroy_Args d;
  memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Executable_Destroy_Args_STRUCT_SIZE;
  d.executable = g.executable;
  api->PJRT_Executable_Destroy(&d);
  CHECK_PJRT_RC(api, nerr);
  reg.num_results = n.num_outputs;
  return int(r->progs.size()) - 1;
}

Runner* create_impl(const char* plugin_so, const char* code, size_t code_size,
                    const char* options_spec) {
  Options opts(options_spec);
  auto r = std::make_unique<Runner>();
  r->dl = dlopen(plugin_so, RTLD_NOW | RTLD_LOCAL);
  if (r->dl == nullptr) {
    g_err = std::string("dlopen failed: ") + dlerror();
    return nullptr;
  }
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(r->dl, "GetPjrtApi"));
  if (get_api == nullptr) {
    g_err = "plugin exports no GetPjrtApi symbol";
    return nullptr;
  }
  r->api = get_api();
  if (r->api == nullptr) {
    g_err = "GetPjrtApi returned null";
    return nullptr;
  }
  const PJRT_Api* api = r->api;
  if (api->pjrt_api_version.major_version != PJRT_API_MAJOR) {
    g_err = "PJRT API major version mismatch: plugin " +
            std::to_string(api->pjrt_api_version.major_version) +
            " vs header " + std::to_string(PJRT_API_MAJOR);
    return nullptr;
  }

  {
    PJRT_Plugin_Initialize_Args a;
    memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    CHECK_PJRT(api, api->PJRT_Plugin_Initialize(&a));
  }
  {
    PJRT_Client_Create_Args a;
    memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
    a.create_options = opts.named.empty() ? nullptr : opts.named.data();
    a.num_options = opts.named.size();
    CHECK_PJRT(api, api->PJRT_Client_Create(&a));
    r->client = a.client;
  }
  {
    PJRT_Client_AddressableDevices_Args a;
    memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    a.client = r->client;
    CHECK_PJRT(api, api->PJRT_Client_AddressableDevices(&a));
    if (a.num_addressable_devices == 0) {
      g_err = "plugin reports no addressable devices";
      return nullptr;
    }
    r->num_devices = a.num_addressable_devices;
    r->device = a.addressable_devices[0];
  }
  if (code != nullptr && code_size > 0) {
    if (compile_program(r.get(), code, code_size) < 0) return nullptr;
  }
  return r.release();
}

// Await + destroy an event; records g_err and returns false on error.
bool await_event(const PJRT_Api* api, PJRT_Event* ev) {
  if (ev == nullptr) return true;
  bool ok = true;
  {
    PJRT_Event_Await_Args a;
    memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
    a.event = ev;
    PJRT_Error* e = api->PJRT_Event_Await(&a);
    if (e != nullptr) {
      PJRT_Error_Message_Args m;
      memset(&m, 0, sizeof(m));
      m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
      m.error = e;
      api->PJRT_Error_Message(&m);
      g_err.assign(m.message, m.message_size);
      PJRT_Error_Destroy_Args d;
      memset(&d, 0, sizeof(d));
      d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
      d.error = e;
      api->PJRT_Error_Destroy(&d);
      ok = false;
    }
  }
  PJRT_Event_Destroy_Args dd;
  memset(&dd, 0, sizeof(dd));
  dd.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dd.event = ev;
  api->PJRT_Event_Destroy(&dd);
  return ok;
}

// Destroys registered device buffers at scope exit — every error path
// after a transfer otherwise leaks device memory (a retrying server
// would OOM the chip).
struct BufGuard {
  const PJRT_Api* api;
  std::vector<PJRT_Buffer*> bufs;

  explicit BufGuard(const PJRT_Api* a) : api(a) {}
  void add(PJRT_Buffer* b) { if (b != nullptr) bufs.push_back(b); }
  ~BufGuard() {
    for (PJRT_Buffer* b : bufs) {
      PJRT_Buffer_Destroy_Args d;
      memset(&d, 0, sizeof(d));
      d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
      d.buffer = b;
      api->PJRT_Buffer_Destroy(&d);
    }
  }
};

bool to_pjrt_type(int32_t dt, PJRT_Buffer_Type* out, int64_t* itemsize) {
  switch (dt) {
    case PTPU_DT_F32: *out = PJRT_Buffer_Type_F32; *itemsize = 4; return true;
    case PTPU_DT_I32: *out = PJRT_Buffer_Type_S32; *itemsize = 4; return true;
    case PTPU_DT_I64: *out = PJRT_Buffer_Type_S64; *itemsize = 8; return true;
    case PTPU_DT_PRED: *out = PJRT_Buffer_Type_PRED; *itemsize = 1;
      return true;
    case PTPU_DT_U8: *out = PJRT_Buffer_Type_U8; *itemsize = 1; return true;
    case PTPU_DT_F64: *out = PJRT_Buffer_Type_F64; *itemsize = 8; return true;
    default: return false;
  }
}

int32_t from_pjrt_type(PJRT_Buffer_Type t) {
  switch (t) {
    case PJRT_Buffer_Type_F32: return PTPU_DT_F32;
    case PJRT_Buffer_Type_S32: return PTPU_DT_I32;
    case PJRT_Buffer_Type_S64: return PTPU_DT_I64;
    case PJRT_Buffer_Type_PRED: return PTPU_DT_PRED;
    case PJRT_Buffer_Type_U8: return PTPU_DT_U8;
    case PJRT_Buffer_Type_F64: return PTPU_DT_F64;
    default: return -1;
  }
}

int execute_n_impl(Runner* r, int32_t prog_i, const ptpu_pjrt_tensor* args,
                   int32_t num_args, ptpu_pjrt_tensor* results,
                   int32_t num_results) {
  const PJRT_Api* api = r->api;
  Runner::Prog* prog = r->prog(prog_i);
  if (prog == nullptr || prog->exec == nullptr) {
    g_err = "no compiled program at index " + std::to_string(prog_i);
    return -1;
  }
  if (num_results > int32_t(prog->num_results)) {
    g_err = "module has " + std::to_string(prog->num_results) +
            " results, caller asked for " + std::to_string(num_results);
    return -1;
  }
  BufGuard guard(api);
  // host -> device, one typed buffer per arg
  std::vector<PJRT_Buffer*> arg_bufs(size_t(num_args), nullptr);
  for (int32_t i = 0; i < num_args; ++i) {
    const ptpu_pjrt_tensor& t = args[i];
    PJRT_Buffer_Type bt;
    int64_t isz = 0;
    if (t.rank < 0 || t.rank > PTPU_MAX_RANK ||
        !to_pjrt_type(t.dtype, &bt, &isz)) {
      g_err = "arg " + std::to_string(i) + ": bad dtype/rank";
      return -1;
    }
    int64_t elems = 1;
    for (int32_t d = 0; d < t.rank; ++d) elems *= t.dims[d];
    if (t.size_bytes != elems * isz) {
      g_err = "arg " + std::to_string(i) + ": size_bytes " +
              std::to_string(t.size_bytes) + " != dims product " +
              std::to_string(elems * isz);
      return -1;
    }
    PJRT_Client_BufferFromHostBuffer_Args a;
    memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    a.client = r->client;
    a.data = t.data;
    a.type = bt;
    a.dims = t.dims;
    a.num_dims = size_t(t.rank);
    a.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    a.device = r->device;
    CHECK_PJRT_RC(api, api->PJRT_Client_BufferFromHostBuffer(&a));
    arg_bufs[i] = a.buffer;
    guard.add(a.buffer);
    if (!await_event(api, a.done_with_host_buffer)) return -1;
  }
  // execute
  std::vector<PJRT_Buffer*> outputs(prog->num_results, nullptr);
  {
    PJRT_ExecuteOptions opts;
    memset(&opts, 0, sizeof(opts));
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
    PJRT_Buffer* const* const arg_lists[] = {arg_bufs.data()};
    PJRT_Buffer** out_list = outputs.data();
    PJRT_Buffer** const out_lists[] = {out_list};
    PJRT_Event* done = nullptr;
    PJRT_LoadedExecutable_Execute_Args a;
    memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    a.executable = prog->exec;
    a.options = &opts;
    a.argument_lists = arg_lists;
    a.num_devices = 1;
    a.num_args = size_t(num_args);
    a.output_lists = out_lists;
    a.device_complete_events = &done;
    a.execute_device = nullptr;  // the compile-time device owns it
    PJRT_Error* err = api->PJRT_LoadedExecutable_Execute(&a);
    for (PJRT_Buffer* b : outputs) guard.add(b);
    if (err != nullptr) {
      PJRT_Error_Message_Args m;
      memset(&m, 0, sizeof(m));
      m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
      m.error = err;
      api->PJRT_Error_Message(&m);
      g_err.assign(m.message, m.message_size);
      PJRT_Error_Destroy_Args dd;
      memset(&dd, 0, sizeof(dd));
      dd.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
      dd.error = err;
      api->PJRT_Error_Destroy(&dd);
      return -1;
    }
    if (!await_event(api, done)) return -1;
  }
  // device -> host: fill every requested result's metadata first, then
  // copy those that fit; -2 when any didn't (caller retries right-sized)
  bool too_small = false;
  for (int32_t i = 0; i < num_results; ++i) {
    ptpu_pjrt_tensor& t = results[i];
    {
      PJRT_Buffer_ElementType_Args a;
      memset(&a, 0, sizeof(a));
      a.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
      a.buffer = outputs[i];
      CHECK_PJRT_RC(api, api->PJRT_Buffer_ElementType(&a));
      t.dtype = from_pjrt_type(a.type);
    }
    {
      PJRT_Buffer_Dimensions_Args a;
      memset(&a, 0, sizeof(a));
      a.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
      a.buffer = outputs[i];
      CHECK_PJRT_RC(api, api->PJRT_Buffer_Dimensions(&a));
      if (a.num_dims > PTPU_MAX_RANK) {
        g_err = "result " + std::to_string(i) + ": rank > PTPU_MAX_RANK";
        return -1;
      }
      t.rank = int32_t(a.num_dims);
      for (size_t d = 0; d < a.num_dims; ++d) t.dims[d] = a.dims[d];
    }
    // ask for dense row-major bytes: with no host layout the plugin
    // hands back the DEVICE's dimension order, and a TPU keeps e.g. an
    // [8,2] f32 result column-major (seen on a v5e: /v1/infer answered
    // the transpose of every [rows, classes] output)
    std::vector<int64_t> minor_to_major(size_t(t.rank));
    for (int32_t d = 0; d < t.rank; ++d)
      minor_to_major[size_t(d)] = t.rank - 1 - d;
    PJRT_Buffer_MemoryLayout row_major;
    memset(&row_major, 0, sizeof(row_major));
    row_major.struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
    row_major.type = PJRT_Buffer_MemoryLayout_Type_Tiled;
    row_major.tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
    row_major.tiled.minor_to_major = minor_to_major.data();
    row_major.tiled.minor_to_major_size = minor_to_major.size();
    PJRT_Buffer_ToHostBuffer_Args a;
    memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    a.src = outputs[i];
    a.host_layout = &row_major;
    CHECK_PJRT_RC(api, api->PJRT_Buffer_ToHostBuffer(&a));  // size query
    int64_t needed = int64_t(a.dst_size);
    if (needed > t.size_bytes || t.data == nullptr) {
      t.size_bytes = needed;
      too_small = true;
      continue;
    }
    memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    a.src = outputs[i];
    a.host_layout = &row_major;
    a.dst = t.data;
    a.dst_size = size_t(needed);
    CHECK_PJRT_RC(api, api->PJRT_Buffer_ToHostBuffer(&a));
    if (!await_event(api, a.event)) return -1;
    t.size_bytes = needed;
  }
  if (too_small) {
    g_err = "output capacity too small";
    return -2;
  }
  return 0;
}

}  // namespace

extern "C" {

void* ptpu_pjrt_create(const char* plugin_so, const char* mlir_code,
                       int64_t code_size) {
  return create_impl(plugin_so, mlir_code, size_t(code_size), nullptr);
}

// Like ptpu_pjrt_create but with plugin create options, a
// "key=value;key=value" string (all-digit values sent as int64, the
// rest as strings) — some plugins (e.g. proxy transports) require
// options to build a client.
void* ptpu_pjrt_create_opts(const char* plugin_so, const char* mlir_code,
                            int64_t code_size, const char* options) {
  return create_impl(plugin_so, mlir_code, size_t(code_size), options);
}

int ptpu_pjrt_device_count(void* h) {
  return h == nullptr ? -1 : int(static_cast<Runner*>(h)->num_devices);
}

int ptpu_pjrt_num_outputs(void* h) {
  return ptpu_pjrt_num_outputs_prog(h, 0);
}

int ptpu_pjrt_num_outputs_prog(void* h, int32_t prog) {
  if (h == nullptr) return -1;
  Runner::Prog* p = static_cast<Runner*>(h)->prog(prog);
  return (p == nullptr || p->exec == nullptr) ? -1 : int(p->num_results);
}

// Compile an additional module on this runner's client (the serving
// daemon's decode init/step modules beside the forward). NOT
// thread-safe against concurrent executes on the same runner — callers
// serialize (the daemon compiles everything before serving, under its
// process-wide device mutex).
int ptpu_pjrt_add_program(void* h, const char* mlir_code,
                          int64_t code_size) {
  if (h == nullptr) { g_err = "null runner"; return -1; }
  if (mlir_code == nullptr || code_size <= 0) {
    g_err = "empty program";
    return -1;
  }
  return compile_program(static_cast<Runner*>(h), mlir_code,
                         size_t(code_size));
}

int ptpu_pjrt_execute_n(void* h, const ptpu_pjrt_tensor* args,
                        int32_t num_args, ptpu_pjrt_tensor* results,
                        int32_t num_results) {
  return ptpu_pjrt_execute_prog(h, 0, args, num_args, results, num_results);
}

int ptpu_pjrt_execute_prog(void* h, int32_t prog,
                           const ptpu_pjrt_tensor* args, int32_t num_args,
                           ptpu_pjrt_tensor* results, int32_t num_results) {
  if (h == nullptr) { g_err = "null runner"; return -1; }
  return execute_n_impl(static_cast<Runner*>(h), prog, args, num_args,
                        results, num_results);
}

// Legacy 1xf32-in/1-out shim (pre-r15 ABI): first result only, element
// count (not bytes) reported; -1 with *out_elems = required elements on
// a short buffer, matching the old retry contract.
int ptpu_pjrt_execute(void* h, const float* in, int64_t rows, int64_t cols,
                      float* out, int64_t capacity, int64_t* out_elems) {
  if (h == nullptr) { g_err = "null runner"; return -1; }
  ptpu_pjrt_tensor a;
  memset(&a, 0, sizeof(a));
  a.dtype = PTPU_DT_F32;
  a.rank = 2;
  a.dims[0] = rows;
  a.dims[1] = cols;
  a.data = const_cast<float*>(in);
  a.size_bytes = rows * cols * int64_t(sizeof(float));
  ptpu_pjrt_tensor res;
  memset(&res, 0, sizeof(res));
  res.data = out;
  res.size_bytes = capacity * int64_t(sizeof(float));
  int rc = execute_n_impl(static_cast<Runner*>(h), 0, &a, 1, &res, 1);
  if (rc == 0 || rc == -2)
    *out_elems = res.size_bytes / int64_t(sizeof(float));
  return rc == 0 ? 0 : -1;
}

void ptpu_pjrt_destroy(void* h) { delete static_cast<Runner*>(h); }

const char* ptpu_pjrt_last_error(void) { return g_err.c_str(); }

}  // extern "C"
